"""The steps of `glm47f-train`, `laguna-train`, `mellum2-train-16k`,
`sdar-train-8k` and `olmoe-train` for a described v5e (tests/v5e_steps.py).
THE LANE READS THE LOWERED MODULES (PR 54 for GLM-4.7-Flash's and Mellum2's,
PR 55 for SDAR's, PR 68 for the rest: one lowering a step, no compile).
GLM-4.7-Flash's (8 of 64 experts and an eighth of the vocabulary held) at
the cell's depth and batch: the text it had, and MLA through the flash
kernels at heads of 256, the held experts' kernels on the compact path and
the second head. Laguna-S-2.1's (the dense layer + one period of three
sliding and one full expert layers, 8 of 256 experts held, 1 x 4096) at the
cell's five layers: the text it had, the kernels by site, the shapes.
Mellum2-12B-A2.5B's (PR 53: one period of three sliding and one full layer
through the same models/laguna.py, 8 of 64 experts and an eighth of the
vocabulary held, 1 x 16,384) at the cell's four layers. SDAR's (PR 55) at
its four. OLMoE-1B-7B's (one layer, batch 6): the text it lowers to, its
nine tiled grouped matmuls, the VMEM the step asks the compiler for. What
only a compile shows is ONE case a step marked `slow`
(`python -m pytest -m slow tests/test_glm47f_laguna_steps_compile.py`:
Laguna's at TWO layers, the dense one and one sliding expert layer, 50 s
alone on this sandbox; OLMoE's, 30 s; SDAR's, 42 s; PR 68): the
layouts at the kernels' door, a block's two branches, a block's bytes, the
tiles of OLMoE's matmul fusions, the scopes that outlive XLA's fusion.
GLM-4.7-Flash's and Mellum2's steps are compiled by no test (PR 54, PR 53).
Every PR's run of the five cells on the chip shows the same (`train_tok_s`,
`hbm_peak_gib.train`, the step's table by scope)."""

import re

import pytest

from v5e_steps import (called_from, grouped_kernels, matmul_tiles, scopes_lost,  # noqa: F401
                       train_step, v5e)

GLM_SHARE = dict(model="glm-4.7-flash", vocab_size=19456, experts_held=8)
# sha256 of the lowered step of glm-4.7-flash as `glm47f-train` builds it (the dense layer,
# four expert layers and the MTP block, 8 of 64 experts and an eighth of the vocabulary held,
# batch 2), as PR 40 lowers it: replaced ON PURPOSE, its five expert blocks are built with the
# compact path (8 of 64 held: a `cond` over 8,192 of 32,768 pair rows); from commit 955060c
# (the parent of PR 38, whose branch CCA and MLA bypass) to PR 39 it was e02a2611...; and as
# PR 44 lowers it: replaced ON PURPOSE again, the sum of its 8,192 held rows into 8,192
# tokens is the band where it was the [8192, 8192] one-hot product (9ff87ef7... from PR 40);
# the account of every hash is tests/test_m7b_steps_compile.py's
# Replaced ON PURPOSE by PR 59: the chosen experts' scores are picked by a compare and a sum
# (`moe._of_chosen`) where `take_along_axis` gathered them one by one (a3bebfc7... from PR 44)
# Replaced ON PURPOSE by PR 63: the band that sums the held rows into their tokens takes a
# window of 256 x C / N rows a block (256 where 1,024 stood) and as many windows as a block's run is
# long (`moe._sum_by_band`: a `fori_loop` inside `lax.map`), rows past the held pairs name no
# token (`moe._held_rows`), and the layer's statistics carry `band_trips` (7f65243a... from PR 59)
_GLM_LITE_STEP = "48e7f6e3c9784587b3858e0b3e2b8d892e68987f0596877f0b894c1ad7110458"
LAGUNA = dict(batch=1, model="laguna-s-2.1", n_layers=5, vocab_size=12544, experts_held=8)
# the dense full-attention layer and ONE sliding expert layer: what the slow case compiles (46 s
# of every core alone where the cell's five layers take 105, 286 CPU s where they take 585: PR 54)
LAGUNA_2 = {**LAGUNA, "n_layers": 2}
# sha256 of that step's lowered text: the other configuration whose stack goes through
# models/llama.py's seam (`stack_module`, PR 46), lowered by PR 46 AND by its parent (5c794fa)
# to the same text, and by PR 47, which adds two names to `llama._remat`'s list that no other
# program carries, and by PR 48, which touches nothing another model imports
# Replaced ON PURPOSE by PR 59: the chosen experts' scores are picked by a compare and a sum
# (`moe._of_chosen`) where `take_along_axis` gathered them one by one (0b2bb23b... from PR 46)
# Replaced ON PURPOSE by PR 63: the band that sums the held rows into their tokens takes a
# window of 256 x C / N rows a block (256 where all 2,560 rows of C stood) and as many windows as a block's run is
# long (`moe._sum_by_band`: a `fori_loop` inside `lax.map`), rows past the held pairs name no
# token (`moe._held_rows`), and the layer's statistics carry `band_trips` (c6f0f50e... from PR 59); with that
# window `moe._sum_is_linear` prices the band under the one-hot product here too (0.155 against
# 0.334 ms a call alone on the chip), so every site of this step is built with the band
_LAGUNA_STEP = "515df84d7243688e3e125aa2e136cb4315aebc01ca08289eb09035eb5f0c57bd"
MELLUM2 = dict(batch=1, model="mellum2-12b-a2.5b", n_layers=4, seq=16384, vocab_size=12288,
               experts_held=8)
# sha256 of the lowered step of mellum2-12b-a2.5b as `mellum2-train-16k` builds it (PR 53: the
# rehearsal's rung (b)); rung (a), 16 held and a quarter of the vocabulary, lowered to 8e98e744...
# Replaced ON PURPOSE by PR 56: its 16,384 keys are ONE kv block, so each of its four layers'
# backward is one fused kernel where the dq and the dk/dv kernels stood, and the kernels carry
# their VMEM limits (a0a2e204... from PR 53 to PR 55)
# Replaced ON PURPOSE by PR 59: the chosen experts' scores are picked by a compare and a sum
# (`moe._of_chosen`) where `take_along_axis` gathered them one by one (535bbc49... from PR 56)
# Replaced ON PURPOSE by PR 63: the band that sums the held rows into their tokens takes a
# window of 256 x C / N rows a block (512 where 2,048 stood) and as many windows as a block's run is
# long (`moe._sum_by_band`: a `fori_loop` inside `lax.map`), rows past the held pairs name no
# token (`moe._held_rows`), and the layer's statistics carry `band_trips` (73b53db5... from PR 59)
_MELLUM2_STEP = "632b53853c1eda7c7efe04cc7c61a08b06fdf6f360a65872b84715d83a6a9786"
# `sdar-train-8k`'s step (PR 55: the rehearsal's rung (a)): 4 full layers of the same module
# trained by block diffusion, 16 of 128 experts and Keye's eighth of the vocabulary held, ONE
# sequence of 8,192 tokens = 16,384 rows
SDAR = dict(batch=1, model="sdar-30b-a3b", n_layers=4, seq=8192, vocab_size=19072,
            experts_held=16)
# sha256 of that step's lowered text (the only step under the block-diffusion objective), as PR
# 56's tree lowers it: recorded on the parent of PR 57 before that PR moved the dense prefix
# out of models/mla.py and the mixer kinds of models/llama.py into one table, and held by it
# Replaced ON PURPOSE by PR 59: the chosen experts' scores are picked by a compare and a sum
# (`moe._of_chosen`) where `take_along_axis` gathered them one by one (523e528c... from PR 56)
# Replaced ON PURPOSE by PR 63: the band that sums the held rows into their tokens takes a
# window of 256 x C / N rows a block (512 where 2,048 stood) and as many windows as a block's run is
# long (`moe._sum_by_band`: a `fori_loop` inside `lax.map`), rows past the held pairs name no
# token (`moe._held_rows`), and the layer's statistics carry `band_trips` (c4789e03... from PR 59)
# Replaced ON PURPOSE by PR 67: the noised blocks' own keys are merged on the arrays as they lie
# (`flash._blockdiff_merge`: `_own_rows`' 0/1 products, `_merge_own_blocks` and its written
# backward behind barriers) where [.., 2048, 4, 128] views stood (a08ccfd5... from PR 63)
_SDAR_STEP = "4ac46fcb1438fcab28156aebbc45b56ae4c458533da2ff129ec58a765cc5eb51"
SDAR_SCOPES = ("diff.corrupt", "diff.loss", "attn.qkv", "attn.norm", "attn.rope", "attn.attend",
               "flash.blockdiff", "attn.out", "moe.router", "moe.dispatch", "moe.experts",
               "moe.combine", "block.norm", "block.stack", "embed", "head", "optim")
OLMOE = dict(batch=6, model="olmoe-1b-7b", n_layers=1)
# sha256 of the lowered step of olmoe-1b-7b as `olmoe-train` builds it (one layer, batch 6):
# the dense steps' block with the q/k norm, so PR 38's text too (36d2bc29... from PR 33's
# parent to PR 37); the account of every hash is tests/test_m7b_steps_compile.py's
_OLMOE_STEP = "9cbdafe7fcffbc7f1b855fa71c37223b22ce43b219d133479411c4ab59756fe8"
GLM_SCOPES = (
    "mla.down", "mla.up", "mla.glue", "mla.attend", "mla.out", "shared.ffn", "moe.router",
    "moe.dispatch", "moe.experts", "moe.combine", "mtp.merge", "mtp.block", "mtp.head")
LAGUNA_SCOPES = (
    "attn.qkv", "attn.rope", "attn.attend", "attn.gate", "attn.out", "swa.qkv", "swa.rope",
    "swa.attend", "swa.gate", "swa.out", "moe.router", "moe.dispatch", "moe.experts",
    "moe.combine", "shared.ffn", "dense.ffn", "block.norm", "block.stack", "head", "optim")


def test_glm_lite_train_step_lowers_to_the_text_it_had(v5e):
    """The case `glm_lite` of the dense steps' test
    (tests/test_m7b_steps_compile.py): MLA bypasses the full-attention
    branch PR 38 altered; PR 40 and PR 44 MEANT to alter the small shares
    and replaced this hash, each once."""
    assert train_step(v5e, batch=2, n_layers=5, **GLM_SHARE).lowered_hash() == _GLM_LITE_STEP


def test_glm_lite_share_train_step_runs_mla_its_kernels_and_the_second_head(v5e):
    """GLM-4.7-Flash as `glm47f-train` builds it (8 of 64 experts and an
    eighth of the vocabulary held; the dense layer, four expert layers,
    the MTP block and batch 2: the cell's own step, the one the hash
    above is of), LOWERED for the described chip and not compiled
    (PR 54): everything this test ever read but the scopes' survival
    through the compile (the scope cases below say what that gave up) is
    in the lowered module, where a Pallas call is a `tpu_custom_call` under
    its caller's scope and a jitted kernel a function called a site (until
    PR 54 the dense
    layer, ONE expert layer and the MTP block were compiled for it, 224 s
    inside the lane, and neither the memory, the VMEM nor the schedule
    of that compile was read; that Mosaic accepts flash at heads of 256
    and the grouped matmul at [2048, 1536] is
    tests/test_tpu_compile.py's and the chip's, PERF.md section 6).
    Every attention is MLA through the flash kernels at heads of 256,
    named after the scope they are called in; the held experts' grouped
    matmuls are the kernels of ops/grouped_matmul.py with a group's whole
    [2048, 1536] weight matrix, in the scan's layer and in the MTP block;
    XLA's own ragged dot is not there; the scopes the cell's readers sum
    are in the step; and the new sublayers count their sites."""
    step = train_step(v5e, batch=2, n_layers=5, **GLM_SHARE)
    engaged = step.engaged("mla.attn", "moe.ffn", "cca.attn", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot", "moe.compact", "moe.full")
    # the dense layer and the expert-layer kind of block, traced once for the scan and the
    # MTP block alike (the rematerialised block is one function): two sites of MLA at least
    assert engaged["mla.attn"] >= 2 and engaged["moe.ffn"] >= 1 and engaged["cca.attn"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    text, kernels = step.lowered_text, step.lowered_kernels
    # 6 + 6 + 6 until PR 40: each of the two sites is now built with the compact path, the
    # branch over the held rows with its nine, the branch over all rows with eleven (3
    # forward, then gate and up again + 3 + 3 backward: it keeps nothing)
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    assert grouped_kernels(kernels) == (["ragged-dot-tiled"] * 2 * (3 + 3 + 2)
                                        + ["ragged-dot-tiled-dgrad"] * 2 * (3 + 3)
                                        + ["ragged-dot-tiled-wgrad"] * 2 * (3 + 3)), kernels
    assert "ragged_dot" not in text   # `lax.ragged_dot`, which compiles to XLA's ragged-dot-none
    # what is no grouped matmul is flash, forward and backward at each of the three sites
    rest = [k for k in kernels if not k.startswith("ragged-dot")]
    assert rest == ["mla.attend"] * 6, kernels
    assert "tensor<2x20x4096x256xbf16>" in text
    # 8 held experts' weights and no more, the router's 64 outputs whole
    assert "8x2048x1536x" in text and "64x2048x1536x" not in text and "4096x64x" in text
    # the MTP block's own sublayers sit inside its scope
    assert any("mtp.block" in n and "mla.attend" in n for n in step.lowered_op_names)
    assert any("mtp.block" in n and "moe.experts" in n for n in step.lowered_op_names)


def test_laguna_train_step_lowers_through_the_seam_to_the_parents_text(v5e):
    """Lowered only (ten seconds, and the kernels' case below lowers no second
    time): the seam is Python dispatch at trace time."""
    assert train_step(v5e, **LAGUNA).lowered_hash() == _LAGUNA_STEP


def test_laguna_share_train_step_runs_window_and_full_kernels_head_major(v5e):
    """Laguna-S-2.1 as `laguna-train` builds it (8 of 256 experts and an
    eighth of the vocabulary held; the dense full-attention layer and the
    cell's period of four: three sliding expert layers and a full one),
    LOWERED for the described chip at the cell's five layers: a sliding
    layer's attention is the
    flash kernels under a window, named `swa.attend`, a full layer's
    `attn.attend`, at 72 and 48 heads of an explicit 128; the held
    experts' grouped matmuls are the kernels of ops/grouped_matmul.py at
    [3072, 1024]; no site falls back. Since PR 40 an expert block is
    built with the compact path (`moe.compact`): a `cond` whose one branch
    runs the nine kernels over the 2,560 held rows and whose other, the
    same block over all 40,960, runs eleven (its backward keeps nothing
    and runs gate and up again). The counts, a block and for the step's
    four expert blocks (PR 54: a Pallas call is a `tpu_custom_call` under its
    caller's scope in the lowered module, a jitted kernel a function
    called a site): forward 3 + 3, backward 6 + 8 a block, of which
    `ragged-dot-tiled` is 3 + 3 + 2, `-dgrad` 3 + 3 and `-wgrad` 3 + 3:
    4 x (8 + 6 + 6) = 80; flash forward and backward a layer: 2 full
    layers (the dense one and the period's last) x 2 = 4 `attn.attend`,
    3 sliding x 2 = 6 `swa.attend`. That q, k, v and o meet no transpose
    and no copy at the kernel's door is the compiler's to show: the slow
    case below, at the dense layer and ONE sliding expert layer."""
    cell = train_step(v5e, **LAGUNA)
    engaged = cell.engaged("laguna.attn", "moe.ffn", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot", "tp_overlap.plain", "moe.compact",
                           "moe.full")
    # a site a layer at least: five attention sublayers, four expert blocks
    assert engaged["laguna.attn"] >= 5 and engaged["moe.ffn"] >= 4
    assert engaged["moe.compact"] >= 4 and engaged["moe.full"] == 0
    assert engaged["grouped_matmul.kernel"] > 0
    assert engaged["grouped_matmul.ragged_dot"] == engaged["tp_overlap.plain"] == 0  # fallback_sites
    # 3 + 3 + 3 a block until PR 40: now the branch over the held rows has those nine and
    # the branch over all rows 3 forward, then gate and up again + 3 + 3 backward
    text, kernels = cell.lowered_text, cell.lowered_kernels
    assert grouped_kernels(kernels) == (["ragged-dot-tiled"] * 4 * (3 + 3 + 2)
                                        + ["ragged-dot-tiled-dgrad"] * 4 * (3 + 3)
                                        + ["ragged-dot-tiled-wgrad"] * 4 * (3 + 3)), kernels
    assert "ragged_dot" not in text   # `lax.ragged_dot`: XLA's ragged-dot-none
    # what is no grouped matmul is flash: forward and backward of each layer, by its scope
    rest = sorted(k for k in kernels if not k.startswith("ragged-dot"))
    assert rest == ["attn.attend"] * 2 * 2 + ["swa.attend"] * 3 * 2, kernels
    assert "1x72x4096x128xbf16" in text and "1x48x4096x128xbf16" in text
    # 8 held experts' weights and no more, the router's 256 outputs whole
    assert "8x3072x1024x" in text and "256x3072x1024x" not in text and "4096x256x" in text


def test_laguna_share_train_step_sizes_the_expert_layer_by_the_held_rows(v5e):
    """The step of `laguna-train` (8 of 256 experts held, 1 x 4096) as it is
    LOWERED at the cell's five layers: every site is built compact, with the
    sum of the held rows into their tokens as the BAND (PR 63: a window of
    256 x 2560 / 4096 -> 256 rows a block of 256 tokens; from PR 44 to PR 62
    the one-hot product, because 256 tokens x top-10 rows were all of C and
    the band the product in a loop), and none falls back to `ragged_dot`;
    the held rows' arrays are [2560, 1024] and [2560, 3072]. What each
    BRANCH of the compiled `cond` holds, and the two layers' bytes, are the
    slow case's."""
    step = train_step(v5e, **LAGUNA)
    engaged = step.engaged("moe.compact", "moe.full", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot", "moe.sum.product", "moe.sum.linear")
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    # at [4096, 2560] the sum of the held rows is the band of 256-row windows (PR 63)
    assert engaged["moe.sum.linear"] >= 2 and engaged["moe.sum.product"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    text = step.lowered_text
    assert "2560x1024xbf16" in text and "2560x3072xbf16" in text
    assert any("moe.held" in n for n in step.lowered_op_names)
    assert any("moe.all" in n for n in step.lowered_op_names)


@pytest.mark.slow
def test_laguna_share_train_step_compiles_head_major_and_sizes_its_branches(v5e):
    """The step of `laguna-train` at the dense layer and ONE sliding expert
    layer COMPILED, outside the tier-1 clock (PR 40; at the cell's five
    layers until PR 54: what is read here is a block's, and every block is
    built by the same code; five layers cost 585 CPU s where two cost 286).
    The kernels stand at the lowered module's sites (8 + 6 + 6 grouped
    matmuls, 2 + 2 flash kernels); q, k, v and o meet no transpose and no
    copy at the kernel's door. The expert block branches once forward and
    once backward (the forward's branch is not run again to differentiate
    it); the branch over the held rows holds NO array of the 40,960 pair
    rows at model or expert width ([40960, 3072], [40960, 1024], [4096, 10
    or 16, 3072]) and runs the block's nine kernels over 2,560 rows; the
    other branch is today's block, whole; and the two layers take no more
    memory than 4.28 GiB of arguments + 2.50 of temporaries (my compile,
    PR 54; the cell's five layers 9.06 + 3.88, PR 40: the branch over all
    rows keeps its temporaries, the kept gate / up are [2560, 1024] a
    block; that the cell's depth fits is the chip's own run's to show,
    10,119,977,984 B at its peak, ledger, PR 53). Every scope the cell's
    readers sum outlives the compile."""
    step = train_step(v5e, **LAGUNA_2)
    hlo, kernels, computations = step.hlo, step.kernels, step.computations
    assert grouped_kernels(kernels) == (["ragged-dot-tiled"] * (3 + 3 + 2)
                                        + ["ragged-dot-tiled-dgrad"] * (3 + 3)
                                        + ["ragged-dot-tiled-wgrad"] * (3 + 3)), kernels
    assert "ragged-dot-none" not in hlo
    rest = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert rest == ["attn.attend"] * 2 + ["swa.attend"] * 2, kernels
    assert re.search(r"bf16\[1,72,4096,128\]", hlo) and re.search(r"bf16\[1,48,4096,128\]", hlo)
    # 8 held experts' weights and no more, the router's 256 outputs whole
    assert "8,3072,1024]" in hlo and "256,3072,1024]" not in hlo and "4096,256]" in hlo
    # head-major from the projections to `wo`: every [1, heads, 4096, 128] array has the
    # tokens and a head's channels as its tile, and none of them, nor a [1, 4096, heads, 128]
    # one, is the result of a copy or a transpose
    moved = [shape for shape, op in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose)\(", hlo, re.M)
        if re.search(r"\[1,(?:72|48|8),4096,128\]|\[1,4096,(?:72|48|8),128\]", shape)]
    assert not moved, moved
    assert set(re.findall(r"bf16\[1,(?:72|48|8),4096,128\]\{([\d,]+)", hlo)) == {"3,2,1,0"}
    branches = re.findall(
        r" conditional\([^\n]*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}", hlo)
    assert len(branches) == 2, branches
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
    assert sorted(ran) == [(3, 3), (6, 8)], ran
    assert step.memory.argument_size_in_bytes < 4.29 * 2 ** 30
    assert step.memory.temp_size_in_bytes < 2.58 * 2 ** 30
    assert not scopes_lost(step, LAGUNA_SCOPES)


def test_mellum2_train_step_lowers_to_one_kv_block_and_the_fused_backward(v5e):
    """The tenth cell's step, LOWERED for the described chip and not compiled
    (its compile is 60 s of every core, and the lane has none to spare:
    ROADMAP D8; the rehearsal's compile is in the configuration file's
    `reduced`, and the kernels at these shapes compile in
    tests/test_tpu_compile.py): the text PR 53 lowered, so that a later
    change to the typed stack, to flash's kv-block rule or to the compact
    path that means to leave this cell alone shows it here; and what the
    lowering itself counts: the SAME module as Laguna's (`laguna.attn`),
    16,384 keys as ONE kv block since PR 56 (4 MiB a k block, within
    `KV_BLOCK_BYTES`), so that every layer's backward is the fused kernel
    (`flash.bwd_fused` 4 and no `flash.bwd_split`: `fallback_sites.train`
    reads 0 where it read 4 a lowering; PERF.md section 6, PR 56), two
    kernels a layer kind and direction of the scan where three stood, the
    expert blocks built compact over C =
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
    assert (engaged["flash.bwd_fused"], engaged["flash.bwd_split"]) == (4, 0)
    # a forward and a fused backward a layer: three window layers and the full one
    kernels = step.lowered_kernels
    assert (kernels.count("swa.attend"), kernels.count("attn.attend")) == (6, 2)
    assert engaged["moe.sum.linear"] >= 2 and engaged["moe.sum.product"] == 0
    text = step.lowered_text
    # 8 held experts' weights and no more, the router's 64 outputs whole, q at one head count
    assert "8x2304x896x" in text and "64x2304x896x" not in text and "2304x64x" in text
    assert "1x32x16384x128xbf16" in text and "1x4x16384x128xbf16" in text


def test_sdar_train_step_lowers_to_the_masked_kernels_and_the_fused_backward(v5e):
    """The eleventh cell's step, LOWERED for the described chip (its compile
    is 60 s of every core: the case marked slow below, and the rehearsal in
    the configuration file's `reduced`): the SAME module as Laguna's and
    Mellum2's (`laguna.attn`), the flash kernels under the block-diffusion
    mask at one site a direction of the layer scan under a name of their
    own, 2L = 16,384 rows against the L = 8,192 clean keys in ONE kv block,
    so the backward is the fused kernel and `fallback_sites.train` reads 0;
    the expert blocks built compact over both copies' rows; no site on
    `ragged_dot`; the head on the L noised rows alone."""
    from ray_tpu import obs

    sites = ("laguna.attn", "moe.ffn", "grouped_matmul.kernel", "grouped_matmul.ragged_dot",
             "moe.compact", "moe.full", "flash.bwd_fused", "flash.bwd_split",
             "flash.blockdiff_merge_tiled", "flash.blockdiff_merge_view")
    count = lambda: {n: obs.layer_counters().get(n, {"count": 0})["count"] for n in sites}  # noqa: E731
    before = count()
    step = train_step(v5e, **SDAR)
    kernels = step.lowered_kernels
    engaged = {n: c - before[n] for n, c in count().items()}
    # the expert layer's `cond`: nine kernels over the held rows, eleven over all rows
    assert kernels.count("flash.blockdiff") == 2 and len(grouped_kernels(kernels)) == 20
    assert engaged["laguna.attn"] >= 1 and engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    assert (engaged["flash.bwd_fused"], engaged["flash.bwd_split"]) == (1, 0)
    # PR 67: the noised blocks of 4 rows are merged on the arrays as they lie at every traced
    # site (the layer scan traces a block once: the traces' count, not the layers'), none on
    # the `[.., 4, 128]` view: a step that silently kept the parent's text would say so here
    assert engaged["flash.blockdiff_merge_tiled"] > 0 and engaged["flash.blockdiff_merge_view"] == 0
    text = step.lowered_text
    # both copies' rows through the layers, the clean keys alone through the kernels, the
    # noised rows alone through the head, 16 held experts' weights and the router's 128 outputs
    assert "1x32x16384x128xbf16" in text and "1x4x8192x128xbf16" in text
    assert "8192x19072xf32" in text and "16384x19072xf32" not in text
    assert "16x2048x768x" in text and "128x2048x768x" not in text and "2048x128x" in text


def test_sdar_train_step_lowers_to_the_text_it_had(v5e):
    """The lowering the case above counted its sites over (tests/v5e_steps.py's
    memo; that case stands first because it counts them), hashed."""
    assert train_step(v5e, **SDAR).lowered_hash() == _SDAR_STEP


@pytest.mark.parametrize("scope", SDAR_SCOPES)
def test_sdar_train_step_keeps_every_scope_its_readers_sum(v5e, scope):
    assert train_step(v5e, **SDAR).has_scope(scope, lowered=True), scope


@pytest.mark.slow
def test_sdar_train_step_compiles_with_mosaics_kernels_and_no_remat_of_the_compilers(v5e):
    """The rehearsal of ISSUE 55's rungs, outside the tier-1 clock (60 s of
    every core): rung (a) COMPILED for the described v5e with the remat
    policy "dots" as it is: the masked flash kernels are Mosaic's (two
    `tpu_custom_call`s named `flash.blockdiff.N`: the forward and the fused
    backward of the layer scan), NONE of the compiler's own
    rematerialisations, and the bytes the configuration file's `reduced`
    states (arguments 5.10 GiB, temporaries 11.45 by the compiler's count,
    which holds both bodies of the expert layer's `cond`; 11.55 since PR 67:
    the noised blocks' own rows of k and v stand in float32 while a layer's
    backward runs, eight arrays of 16 MiB)."""
    step = train_step(v5e, **SDAR)
    masked = [k for k in step.kernels if k.startswith("flash.blockdiff")]
    assert len(masked) == 2 and len(grouped_kernels(step.kernels)) == 20
    assert not re.findall(r"%([\w.\-]+\.remat[\w.\-]*) = ", step.hlo)
    assert step.memory.argument_size_in_bytes < 5.2 * 2 ** 30
    assert step.memory.temp_size_in_bytes < 11.6 * 2 ** 30


def test_olmoe_train_step_lowers_to_the_text_it_had(v5e):
    """The case `olmoe` of the dense steps' test
    (tests/test_m7b_steps_compile.py): OLMoE's step enters flash by the
    old entry and holds every expert, so PR 33, 34, 40 and 44 left it the
    text it had, and PR 38 MEANT to alter it."""
    assert train_step(v5e, **OLMOE).lowered_hash() == _OLMOE_STEP


OLMOE_GROUPED = (["ragged-dot-tiled"] * 3 + ["ragged-dot-tiled-dgrad"] * 3
                 + ["ragged-dot-tiled-wgrad"] * 3)


def test_expert_train_step_runs_nine_tiled_grouped_matmuls(v5e):
    """The OLMoE step of `olmoe-train` (one layer, batch 6) LOWERED for
    the described chip: its grouped matmuls are the kernels of
    ops/grouped_matmul.py, nine of them (forward, input and weight
    gradient of gate, up and down: none recomputed under remat), under
    names a profile's reader classes as the expert layer's
    (`^kernel:ragged-dot` in chipbench/trace_names), and `lax.ragged_dot`,
    XLA's own 512 x 512 x 512 kernel, is gone."""
    step = train_step(v5e, **OLMOE)
    engaged = step.engaged("grouped_matmul.kernel", "grouped_matmul.ragged_dot")
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    kernels = step.lowered_kernels
    grouped = grouped_kernels(kernels)
    assert grouped == OLMOE_GROUPED, kernels
    assert "ragged_dot" not in step.lowered_text
    # what is no grouped matmul is flash: forward, and backward
    assert len(kernels) - len(grouped) == 2, kernels


def test_olmoe_train_step_compiles_with_the_vmem_its_operations_are_given(v5e):
    """`olmoe-train`'s case of the dense steps' test in
    tests/test_m7b_steps_compile.py: the step carries 32 MiB of VMEM an
    operation to its compile and nothing else. What the limit buys is the
    slow case's."""
    assert train_step(v5e, **OLMOE).compiler_options == {"xla_tpu_scoped_vmem_limit_kib": 32 * 1024}


@pytest.mark.slow
def test_olmoe_train_step_compiles_to_nine_kernels_one_schedule_and_half_the_tiles(v5e):
    """`olmoe-train`'s step COMPILED, outside the tier-1 clock: the nine
    grouped matmuls under their names, neither XLA's own kernel nor its
    metadata; one tile schedule a layer and direction, not one a call;
    7.37 GiB of temporaries at the parent (past 8 the compiler
    rematerialises the head); fewer than half the 30,468 tiles its matmul
    fusions have at 16 MiB of VMEM, the temporaries under 6.9 GiB (6.62 at
    16 MiB), and what the limit is bought with: the expert layer's token
    gathers read their 96 MiB table [24576, 2048] from the VMEM no
    operation claims."""
    temp_gib, tiles_at_16 = 6.9, 30468
    step = train_step(v5e, **OLMOE)
    hlo, kernels = step.hlo, step.kernels
    grouped = grouped_kernels(kernels)
    assert grouped == OLMOE_GROUPED, kernels
    assert "ragged-dot-none" not in hlo and "ragged-dot-metadata" not in hlo
    assert len(kernels) - len(grouped) == 2, kernels
    # the schedule's three comparisons of visits with groups: one schedule
    # forward and one backward, where one a call would be nine
    assert len(re.findall(r"pred\[447,64\]\S* compare\(", hlo)) <= 2 * 3
    assert 0 < matmul_tiles(hlo) < 0.5 * tiles_at_16
    assert step.memory.temp_size_in_bytes < temp_gib * 2 ** 30
    in_vmem = [name for name, body in re.findall(
        r"^%(fused_computation[.\d]*) \([^\n]*\{\n(.*?)^\}", hlo, re.M | re.S)
        if re.search(r"= bf16\[24576,2048\]\{[^}]*S\(1\)\} parameter\(0\)", body)
        and " gather(" in body]
    assert len(in_vmem) >= 2, in_vmem


@pytest.mark.parametrize("scope", GLM_SCOPES)
def test_glm_lite_train_step_holds_the_scope_its_readers_sum(v5e, scope):
    """A scope the cell's readers sum is in the LOWERED step (the one lowering
    of the file's other cases of this step: tests/v5e_steps.py's memo), a
    case a scope. Until PR 54 the thirteen were read from the COMPILED
    step's `op_name`s, which is where a trace's readers find them: that a
    scope outlives XLA's fusion (`mla.glue`, `mtp.merge`) is the one
    compiled fact of this step that tier-1 gave up with its compile (224 s
    inside the lane); the chip's traced run of `glm47f-train` shows it,
    `chipbench/step_scopes/`'s table a row a scope."""
    assert train_step(v5e, batch=2, n_layers=5, **GLM_SHARE).has_scope(scope, lowered=True), scope


@pytest.mark.parametrize("scope", LAGUNA_SCOPES)
def test_laguna_share_train_step_holds_the_scope_its_readers_sum(v5e, scope):
    """A scope the cell's readers sum is in the LOWERED step at the cell's five
    layers (the one lowering of the file's other cases of this step:
    tests/v5e_steps.py's memo), a case a scope; that it outlives the compile
    is the slow case's."""
    assert train_step(v5e, **LAGUNA).has_scope(scope, lowered=True), scope


ROUTED_STEPS = {
    "glm47f-train": dict(batch=2, n_layers=5, **GLM_SHARE), "laguna-train": LAGUNA,
    "mellum2-train-16k": MELLUM2, "sdar-train-8k": SDAR,
}


@pytest.mark.parametrize("cell", sorted(ROUTED_STEPS))
def test_a_cells_router_gathers_no_score_and_scatters_none(v5e, cell):
    """No operation of the LOWERED step (the file's one lowering of it) is a
    gather or a scatter traced under `moe.router`: the chosen experts'
    scores are picked by a compare and a sum (`moe._of_chosen`, PR 59). As
    `take_along_axis` they were a gather of single elements, 1.34 ms a
    call of `mellum2-train-16k`'s 131,072 and 0.89 ms its transpose, 14.3
    of the step's 268 ms, which PR 53 to PR 58 read as the router's matmul."""
    names = train_step(v5e, **ROUTED_STEPS[cell]).lowered_op_names
    routed = [n for n in names if "moe.router" in n]
    assert any(n.endswith("/top_k") for n in routed), routed[:5]
    assert not [n for n in routed if re.search(r"/(gather|scatter[\w\-]*)$", n)]
