"""The steps of `glm47f-train` and `laguna-train` for a described v5e
(tests/v5e_steps.py), each compiled ONCE. GLM-4.7-Flash's (8 of 64
experts and an eighth of the vocabulary held): the text the cell's depth
lowers to, and the dense layer, one expert layer and the MTP block
compiled: MLA through the flash kernels at heads of 256, the held
experts' kernels on the compact path, the second head. Laguna-S-2.1's
(the dense layer + one period of three sliding and one full expert
layers, 8 of 256 experts held, 1 x 4096) at the cell's five layers, both
of its tests reading that one text. Mellum2-12B-A2.5B's (PR 53: one
period of three sliding and one full layer through the same
models/laguna.py, 8 of 64 experts and an eighth of the vocabulary held,
1 x 16,384) at the cell's four layers, lowered and compiled once for both
of its tests. The cells stand two or three a file by their compiles'
seconds (ROADMAP D8), not by their kind.""" 

import re

from v5e_steps import called_from, grouped_kernels, train_step, v5e  # noqa: F401 - a fixture

GLM_SHARE = dict(model="glm-4.7-flash", vocab_size=19456, experts_held=8)
# sha256 of the lowered step of glm-4.7-flash as `glm47f-train` builds it (the dense layer,
# four expert layers and the MTP block, 8 of 64 experts and an eighth of the vocabulary held,
# batch 2), as PR 40 lowers it: replaced ON PURPOSE, its five expert blocks are built with the
# compact path (8 of 64 held: a `cond` over 8,192 of 32,768 pair rows); from commit 955060c
# (the parent of PR 38, whose branch CCA and MLA bypass) to PR 39 it was e02a2611...; and as
# PR 44 lowers it: replaced ON PURPOSE again, the sum of its 8,192 held rows into 8,192
# tokens is the band where it was the [8192, 8192] one-hot product (9ff87ef7... from PR 40);
# the account of every hash is tests/test_m7b_steps_compile.py's
_GLM_LITE_STEP = "a3bebfc76d0379f05c0b4184981fd00c826b8233b90f4b9505e2848a85d87357"
LAGUNA = dict(batch=1, model="laguna-s-2.1", n_layers=5, vocab_size=12544, experts_held=8)
MELLUM2 = dict(batch=1, model="mellum2-12b-a2.5b", n_layers=4, seq=16384, vocab_size=12288,
               experts_held=8)
# sha256 of the lowered step of mellum2-12b-a2.5b as `mellum2-train-16k` builds it (PR 53: the
# rehearsal's rung (b)); rung (a), 16 held and a quarter of the vocabulary, lowered to 8e98e744...
_MELLUM2_STEP = "a0a2e204465b7f4b8138cd94b51485d95ebcd8e36613a481dfaede246ebf0d1d"


def test_glm_lite_train_step_lowers_to_the_text_it_had(v5e):
    """The case `glm_lite` of the dense steps' test
    (tests/test_m7b_steps_compile.py): MLA bypasses the full-attention
    branch PR 38 altered; PR 40 and PR 44 MEANT to alter the small shares
    and replaced this hash, each once."""
    assert train_step(v5e, batch=2, n_layers=5, **GLM_SHARE).lowered_hash() == _GLM_LITE_STEP


def test_glm_lite_share_train_step_runs_mla_its_kernels_and_the_second_head(v5e):
    """GLM-4.7-Flash as `glm47f-train` builds it (8 of 64 experts and an
    eighth of the vocabulary held; the dense layer, ONE expert layer and
    the MTP block and one sequence here, the cell's depth and batch are
    rehearsed in PERF.md), compiled for the described chip: every
    attention is MLA through the flash kernels at heads of 256, named
    after the scope they are called in; the held experts' grouped matmuls
    are the kernels of ops/grouped_matmul.py at [2048, 1536] with a
    group's whole weight matrix as one block, in the scan's layer and in
    the MTP block; XLA's own ragged-dot kernel is not there; the scopes
    the cell's readers sum are in the compiled step; and the new
    sublayers count their sites."""
    step = train_step(v5e, batch=1, n_layers=2, **GLM_SHARE)
    engaged = step.engaged("mla.attn", "moe.ffn", "cca.attn", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot", "moe.compact", "moe.full")
    # the dense layer and the expert-layer kind of block, traced once for the scan and the
    # MTP block alike (the rematerialised block is one function): two sites of MLA at least
    assert engaged["mla.attn"] >= 2 and engaged["moe.ffn"] >= 1 and engaged["cca.attn"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    hlo, kernels = step.hlo, step.kernels
    # 6 + 6 + 6 until PR 40: each of the two sites is now built with the compact path, the
    # branch over the held rows with its nine, the branch over all rows with eleven (3
    # forward, then gate and up again + 3 + 3 backward: it keeps nothing)
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    assert grouped_kernels(kernels) == (["ragged-dot-tiled"] * 2 * (3 + 3 + 2)
                                        + ["ragged-dot-tiled-dgrad"] * 2 * (3 + 3)
                                        + ["ragged-dot-tiled-wgrad"] * 2 * (3 + 3)), kernels
    assert "ragged-dot-none" not in hlo
    # what is no grouped matmul is flash, forward and backward at each of the three sites
    rest = [k for k in kernels if not k.startswith("ragged-dot")]
    assert len(rest) == 6 and all("mla.attend" in k for k in rest), kernels
    assert re.search(r"bf16\[1,20,4096,256\]", hlo)
    # 8 held experts' weights and no more, the router's 64 outputs whole
    assert "8,2048,1536]" in hlo and "64,2048,1536]" not in hlo and "4096,64]" in hlo
    for scope in ("mla.down", "mla.up", "mla.glue", "mla.attend", "mla.out", "shared.ffn",
                  "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "mtp.merge",
                  "mtp.block", "mtp.head"):
        assert step.has_scope(scope), scope
    # the MTP block's own sublayers sit inside its scope
    assert any("mtp.block" in n and "mla.attend" in n for n in step.op_names)
    assert any("mtp.block" in n and "moe.experts" in n for n in step.op_names)


def test_laguna_share_train_step_runs_window_and_full_kernels_head_major(v5e):
    """Laguna-S-2.1 as `laguna-train` builds it (8 of 256 experts and an
    eighth of the vocabulary held; the dense full-attention layer and the
    cell's period of four: three sliding expert layers and a full one),
    compiled for the described chip: a sliding layer's attention is the
    flash kernels under a window, named `swa.attend.N`, a full layer's
    `attn.attend.N`, at 72 and 48 heads of an explicit 128; the held
    experts' grouped matmuls are the kernels of ops/grouped_matmul.py at
    [3072, 1024]; every scope the cell's readers sum is in the compiled
    step; q, k, v and o meet no transpose and no copy at the kernel's
    door; no site falls back. Since PR 40 an expert block is built with
    the compact path (`moe.compact`): a `cond` whose one branch runs the
    nine kernels over the 2,560 held rows and whose other, the same block
    over all 40,960, runs eleven (its backward keeps nothing and runs gate
    and up again). The counts, a block and for the step's four expert
    blocks (until PR 45 this test compiled the dense layer and ONE sliding
    expert layer: 8 + 6 + 6 grouped matmuls, 2 + 2 flash kernels):
    forward 3 + 3, backward 6 + 8 a block, of which `ragged-dot-tiled` is
    3 + 3 + 2, `-dgrad` 3 + 3 and `-wgrad` 3 + 3: 4 x (8 + 6 + 6) = 80;
    flash forward and backward a layer: 2 full layers (the dense one and
    the period's last) x 2 = 4 `attn.attend`, 3 sliding x 2 = 6
    `swa.attend`."""
    step = train_step(v5e, **LAGUNA)
    engaged = step.engaged("laguna.attn", "moe.ffn", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot", "tp_overlap.plain", "moe.compact",
                           "moe.full")
    # a site a layer at least: five attention sublayers, four expert blocks
    assert engaged["laguna.attn"] >= 5 and engaged["moe.ffn"] >= 4
    assert engaged["moe.compact"] >= 4 and engaged["moe.full"] == 0
    assert engaged["grouped_matmul.kernel"] > 0
    assert engaged["grouped_matmul.ragged_dot"] == engaged["tp_overlap.plain"] == 0  # fallback_sites
    hlo, kernels = step.hlo, step.kernels
    # 3 + 3 + 3 a block until PR 40: now the branch over the held rows has those nine and
    # the branch over all rows 3 forward, then gate and up again + 3 + 3 backward
    assert grouped_kernels(kernels) == (["ragged-dot-tiled"] * 4 * (3 + 3 + 2)
                                        + ["ragged-dot-tiled-dgrad"] * 4 * (3 + 3)
                                        + ["ragged-dot-tiled-wgrad"] * 4 * (3 + 3)), kernels
    assert "ragged-dot-none" not in hlo
    # what is no grouped matmul is flash: forward and backward of each layer, by its scope
    rest = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert rest == ["attn.attend"] * 2 * 2 + ["swa.attend"] * 3 * 2, kernels
    assert re.search(r"bf16\[1,72,4096,128\]", hlo) and re.search(r"bf16\[1,48,4096,128\]", hlo)
    # 8 held experts' weights and no more, the router's 256 outputs whole
    assert "8,3072,1024]" in hlo and "256,3072,1024]" not in hlo and "4096,256]" in hlo
    for scope in ("attn.qkv", "attn.rope", "attn.attend", "attn.gate", "attn.out", "swa.qkv",
                  "swa.rope", "swa.attend", "swa.gate", "swa.out", "moe.router", "moe.dispatch",
                  "moe.experts", "moe.combine", "shared.ffn", "dense.ffn", "block.norm",
                  "block.stack", "head", "optim"):
        assert step.has_scope(scope), scope
    # head-major from the projections to `wo`: every [1, heads, 4096, 128] array has the
    # tokens and a head's channels as its tile, and none of them, nor a [1, 4096, heads, 128]
    # one, is the result of a copy or a transpose
    moved = [shape for shape, op in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose)\(", hlo, re.M)
        if re.search(r"\[1,(?:72|48|8),4096,128\]|\[1,4096,(?:72|48|8),128\]", shape)]
    assert not moved, moved
    assert set(re.findall(r"bf16\[1,(?:72|48|8),4096,128\]\{([\d,]+)", hlo)) == {"3,2,1,0"}


def test_laguna_share_train_step_sizes_the_expert_layer_by_the_held_rows(v5e):
    """The step of `laguna-train` as the cell builds it (the dense layer +
    one period of four, 8 of 256 experts held, 1 x 4096), compiled for
    the described chip (PR 40). Each of the four expert blocks branches
    once forward and once backward (the forward's branch is not run again
    to differentiate it); the branch over the held rows holds NO array of
    the 40,960 pair rows at model or expert width ([40960, 3072],
    [40960, 1024], [4096, 10 or 16, 3072]) and runs the block's nine
    kernels over 2,560 rows; the other branch is today's block, whole;
    every site is built compact, with the sum of the held rows into
    their tokens as the one-hot product (PR 44: 256 tokens x top-10 rows
    are all of C here, the band would be the product in a loop), and none
    falls back to `ragged_dot`; and the step takes no more memory than its parent's 9.06 GiB of
    arguments + 4.00 of temporaries (3.88: the branch over all rows keeps
    its temporaries, the kept gate / up are [2560, 1024] a block)."""
    step = train_step(v5e, **LAGUNA)
    engaged = step.engaged("moe.compact", "moe.full", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot", "moe.sum.product", "moe.sum.linear")
    assert engaged["moe.compact"] >= 4 and engaged["moe.full"] == 0
    # at [4096, 2560] the sum of the held rows stays the one-hot product (PR 44)
    assert engaged["moe.sum.product"] >= 2 and engaged["moe.sum.linear"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    hlo, computations = step.hlo, step.computations
    branches = re.findall(
        r" conditional\([^\n]*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}", hlo)
    assert len(branches) == 2 * 4, branches
    wide = re.compile(r"(?:bf16|f32)\[(?:40960,(?:3072|1024)|4096,1[06],3072)\]")
    ran = []
    for over_all_rows, over_held_rows in branches:  # `cond`: index 0 is the false branch
        held = "\n".join(computations[c] for c in called_from(computations, over_held_rows))
        every = "\n".join(computations[c] for c in called_from(computations, over_all_rows))
        assert "moe.held" in held and "moe.all" not in held
        assert "moe.all" in every and "moe.held" not in every
        assert not wide.search(held), sorted(set(wide.findall(held)))
        assert wide.search(every)
        assert re.search(r"bf16\[2560,1024\]", held) and re.search(r"bf16\[2560,3072\]", held)
        ran.append(tuple(len(re.findall(r"%(ragged-dot-tiled[\w\-]*)\.\d+ = ", text))
                         for text in (held, every)))
    # forward and backward: nine kernels over the held rows, eleven over all rows
    assert sorted(ran) == [(3, 3)] * 4 + [(6, 8)] * 4, ran
    assert step.memory.argument_size_in_bytes < 9.07 * 2 ** 30
    assert step.memory.temp_size_in_bytes < 4.00 * 2 ** 30


def test_mellum2_train_step_lowers_to_the_text_it_had_over_four_kv_blocks(v5e):
    """The tenth cell's step, LOWERED for the described chip and not compiled
    (its compile is 60 s of every core, and the lane has none to spare:
    ROADMAP D8; the rehearsal's compile is in the configuration file's
    `reduced`, and the kernels at these shapes compile in
    tests/test_tpu_compile.py): the text PR 53 lowered, so that a later
    change to the typed stack, to flash's kv-block rule or to the compact
    path that means to leave this cell alone shows it here; and what the
    lowering itself counts: the SAME module as Laguna's (`laguna.attn`),
    16,384 keys as four kv blocks so that every layer's backward is the dq
    and the dk/dv kernels apart (`flash.bwd_split` 4, which
    `fallback_sites.train` books as fallen back: the cell's subject,
    PERF.md section 6, PR 53), the expert blocks built compact over C =
    32,768 of 131,072 pair rows with the band for their sum, no site on
    `ragged_dot`."""
    from ray_tpu import obs

    sites = ("laguna.attn", "moe.ffn", "grouped_matmul.kernel", "grouped_matmul.ragged_dot",
             "moe.compact", "moe.full", "flash.bwd_fused", "flash.bwd_split", "moe.sum.linear",
             "moe.sum.product")
    count = lambda: {n: obs.layer_counters().get(n, {"count": 0})["count"] for n in sites}  # noqa: E731
    before = count()
    step = train_step(v5e, **MELLUM2)
    assert step.lowered_hash() == _MELLUM2_STEP
    engaged = {n: c - before[n] for n, c in count().items()}
    assert engaged["laguna.attn"] >= 4 and engaged["moe.ffn"] >= 4
    assert engaged["moe.compact"] >= 4 and engaged["moe.full"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    assert (engaged["flash.bwd_fused"], engaged["flash.bwd_split"]) == (0, 4)
    assert engaged["moe.sum.linear"] >= 2 and engaged["moe.sum.product"] == 0
    text = step.lowered_text
    # 8 held experts' weights and no more, the router's 64 outputs whole, q at one head count
    assert "8x2304x896x" in text and "64x2304x896x" not in text and "2304x64x" in text
    assert "1x32x16384x128xbf16" in text and "1x4x16384x128xbf16" in text
