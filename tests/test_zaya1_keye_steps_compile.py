"""The steps of `zaya1-train` and `keye-train-8k` for a described v5e
(tests/v5e_steps.py). ZAYA1-8B's (8 of 16 experts and an eighth of the
vocabulary held): the text the cell's six layers lower to, and one layer:
CCA through the flash kernels, the held experts' kernels. The language
model of Keye-VL-2.0's (16 of 128 experts held, ONE sequence of 8192) at
ONE layer: the flash kernels under a packed selection; and two layers, for
what the stack's scan hands its backward. THE LANE READS THE LOWERED MODULES
(PR 68: one lowering a step, no compile): the hashes, the kernels by site,
the traced sites, the shapes, every scope the cell's readers sum. What only
a compile shows is ONE case a step marked `slow`
(`python -m pytest -m slow tests/test_zaya1_keye_steps_compile.py`, 54 and
47 s alone on this sandbox, PR 68): CCA's mix laid out head-major with no
copy, ZAYA1's temporaries, the 33 MiB of VMEM Keye's fused backward is
given, the band's blocks as they are compiled, the scopes that outlive
XLA's fusion. Every PR's run of the two cells on the chip shows the same
(`train_tok_s`, `hbm_peak_gib.train`, the step's table by scope)."""

import re

import pytest

from v5e_steps import grouped_kernels, scopes_lost, train_step, v5e  # noqa: F401 - a fixture

ZAYA_SHARE = dict(model="zaya1-8b", vocab_size=32896, experts_held=8)
# sha256 of the lowered step of zaya1-8b as `zaya1-train` builds it (six layers, 8 of 16
# experts and an eighth of the vocabulary held, batch 2), as commit 21a2054 (the parent of PR
# 34, which gave the block a third kind of attention, the expert layer a second kind of score
# and the decoder blocks outside its scan) lowers it; the account of every hash is
# tests/test_m7b_steps_compile.py's
_ZAYA_STEP = "5c0e2e3ba71539f56323de421562ccae59c9377d2e3b1531e6a4a2459053d47d"
KEYE = dict(batch=1, model="keye-vl-2.0-30b-a3b", n_layers=1, seq=8192, vocab_size=19072,
            experts_held=16)
# two of the cell's layers, LOWERED only: the stack's scan is there from two layers on
KEYE_2 = {**KEYE, "n_layers": 2}
# sha256 of the lowered step of `keye-train-8k` (the only step through models/dsa.py) at ONE
# layer and at two, as PR 56's tree lowers them: recorded on the parent of PR 57 before that PR
# moved WHERE models/llama.py reads a configuration's mixer kind, and held by it letter for
# letter; the account of every hash is tests/test_m7b_steps_compile.py's
# Replaced ON PURPOSE by PR 59: the chosen experts' scores are picked by a compare and a sum
# (`moe._of_chosen`) where `take_along_axis` gathered them one by one (12fa5d1b... and
# f8c66c23... from PR 56)
# Replaced ON PURPOSE by PR 63: the band that sums the held rows into their tokens takes a
# window of 256 x C / N rows a block (512 where 2,048 stood) and as many windows as a block's run is
# long (`moe._sum_by_band`: a `fori_loop` inside `lax.map`), rows past the held pairs name no
# token (`moe._held_rows`), and the layer's statistics carry `band_trips` (13362874... and af7fa4c6... from PR 59)
_KEYE_STEP = {1: "4cca719d6401ac54c329e79b464c2d583402b158c33940c99638cacf0d477701",
              2: "be336956e9a9962727836eefee16815dec8af725e64488c8cdf145bb19f12445"}
KEYE_SCOPES = (
    "dsa.qkv", "dsa.norm", "dsa.rope", "dsa.index.proj", "dsa.index.scores", "dsa.select",
    "dsa.attend", "dsa.out", "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
    "block.norm", "block.stack", "head", "optim")


def test_zaya_train_step_lowers_to_the_text_it_had(v5e):
    """The case `zaya` of the dense steps' test
    (tests/test_m7b_steps_compile.py): CCA bypasses the full-attention
    branch PR 38 altered, and a half share builds no compact path (PR 40,
    PR 44), so the step keeps the text PR 34's parent gave it."""
    assert train_step(v5e, batch=2, n_layers=6, **ZAYA_SHARE).lowered_hash() == _ZAYA_STEP


ZAYA_GROUPED = (["ragged-dot-tiled"] * 3 + ["ragged-dot-tiled-dgrad"] * 3
                + ["ragged-dot-tiled-wgrad"] * 3)


def test_zaya_share_train_step_runs_its_kernels_and_skips_the_rows_elsewhere(v5e):
    """ZAYA1-8B as `zaya1-train` builds it (8 of 16 experts held, an
    eighth of the vocabulary; ONE layer and one sequence here, the
    cell's six and its batch are rehearsed in PERF.md), LOWERED for
    the described chip: CCA's attention is the two flash kernels, the
    held experts' nine grouped matmuls are the kernels of
    ops/grouped_matmul.py with a group's whole [2048, 2048] weight
    matrix as one block, `lax.ragged_dot` is not there, and both new
    sublayers count their sites. How CCA's mix is laid out, and the
    temporaries, are the slow case's."""
    step = train_step(v5e, batch=1, n_layers=1, **ZAYA_SHARE)
    engaged = step.engaged("cca.attn", "moe.ffn", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot")
    assert engaged["cca.attn"] > 0 and engaged["moe.ffn"] > 0
    assert engaged["grouped_matmul.kernel"] > 0 and engaged["grouped_matmul.ragged_dot"] == 0
    text, kernels = step.lowered_text, step.lowered_kernels
    assert grouped_kernels(kernels) == ZAYA_GROUPED, kernels
    assert "ragged_dot" not in text   # `lax.ragged_dot`, which compiles to XLA's ragged-dot-none
    # what is no grouped matmul is flash, named after the scope it is called in
    rest = [k for k in kernels if not k.startswith("ragged-dot")]
    assert rest == ["cca.attend"] * 2, kernels
    # the router's state beside the hidden state
    assert "1x4096x256xf32" in text
    # 8 held experts' weights and no more: [1, 8, 2048, 2048], never 16
    assert "8x2048x2048x" in text and "16x2048x2048x" not in text


@pytest.mark.slow
def test_zaya_share_train_step_compiles_with_its_mix_head_major_and_no_copy(v5e):
    """The same one-layer step COMPILED, outside the tier-1 clock: the
    kernels Mosaic took stand at the lowered module's sites under their
    names, CCA's mix is laid out with the tokens and a head's channels as
    the tile, XLA's own ragged-dot kernel is not there."""
    step = train_step(v5e, batch=1, n_layers=1, **ZAYA_SHARE)
    hlo, kernels = step.hlo, step.kernels
    assert grouped_kernels(kernels) == ZAYA_GROUPED, kernels
    assert "ragged-dot-none" not in hlo
    rest = [k for k in kernels if not k.startswith("ragged-dot")]
    assert len(rest) == 2 and all(k.startswith("cca.attend") for k in rest), kernels
    # the router's state leaves the forward scan beside the hidden state
    assert re.search(r"f32\[1,4096,256\]", hlo)
    assert "8,2048,2048]" in hlo and "16,2048,2048]" not in hlo
    # CCA's mix holds its heads in a MAJOR dimension (PR 33): wherever an array under
    # `cca.mix` has a head's channels in its lanes, the tokens are in the sublanes, never
    # the 2, 8 or 10 heads (padded to the tile's 8 or 16); and nothing is moved between
    # layouts: the parent had 12 `copy` instructions of activations under that scope in
    # this step ([1, 4096, 10, 128] <-> [10, 1, 4096, 128] and channels-in-sublanes
    # copies), and 0.8522 GiB of temporaries (what is still copied is the taps' weights,
    # [heads, 2, 128, 128])
    mix = [(shape, op) for shape, op, op_name in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\([^\n]*op_name=\"([^\"]*)\"", hlo, re.M)
        if re.search(r"(?:^|/)cca\.mix(?:/|$)", op_name)]
    assert len(mix) > 50
    assert not [shape for shape, op in mix if op in ("copy", "transpose") and "4096" in shape]
    arrays = [([int(d) for d in dims.split(",")], [int(i) for i in order.split(",")])
              for shape, _ in mix
              for dims, order in re.findall(r"(?:bf16|f32)\[([\d,]+)\]\{([\d,]+)", shape)]
    tiles = {(dims[order[1]], dims[order[0]]) for dims, order in arrays
             if len(dims) >= 4 and 4096 in dims and dims[order[0]] != 4096}
    assert tiles and all(rows == 4096 for rows, _ in tiles), tiles
    assert step.memory.temp_size_in_bytes < 0.8522 * 2 ** 30


@pytest.mark.parametrize("n_layers", [1, 2], ids=["one_layer", "two_layers"])
def test_keye_train_step_lowers_to_the_text_it_had(v5e, n_layers):
    """The lowerings the two cases below read (tests/v5e_steps.py's memo: no
    compile and no lowering of this case's own), hashed."""
    assert train_step(v5e, **{**KEYE, "n_layers": n_layers}).lowered_hash() == _KEYE_STEP[n_layers]


def test_keye_share_train_step_runs_the_kernels_under_a_packed_selection(v5e):
    """The language model of Keye-VL-2.0 as `keye-train-8k` builds it (16 of
    128 experts and an eighth of the vocabulary held, ONE sequence of 8192;
    ONE of the cell's layers here), LOWERED for the described chip: the
    attention is the flash kernels under the indexer's selection, named
    `dsa.attend`: one forward and ONE backward (PR 43: 8192 keys at
    heads of 128 in bf16 are one kv block, two selection blocks wide, so
    the backward is the fused kernel, which states the 33 MiB of VMEM its
    blocks need; that Mosaic accepts the block is the slow case's and the
    chip's); the selection reaches them as ONE packed
    int32 [1, 8192, 256] array a layer (8 MiB), stacked over the layers
    for the backward (read from two layers: the scan
    hands its backward a [2, 1, 8192, 256] int32), which computes no index
    score and no top-k again;
    no [.., 8192, 8192] array of any type exists; the held experts' grouped
    matmuls are the kernels of ops/grouped_matmul.py at [2048, 768] on
    the compact path, whose sums of the 16,384 held rows into the 8192
    tokens are built in the LINEAR form at every site (PR 44: no
    [8192, 16384] one-hot matrix is an operand or a result of anything);
    no site falls back."""
    from ray_tpu.ops.flash import _fused_bwd_params

    step = train_step(v5e, **KEYE)
    engaged = step.engaged("dsa.attn", "moe.ffn", "grouped_matmul.kernel",
                           "grouped_matmul.ragged_dot", "tp_overlap.plain", "moe.compact",
                           "moe.full", "flash.bwd_fused", "flash.bwd_split",
                           "moe.sum.linear", "moe.sum.product")
    assert engaged["dsa.attn"] >= 1 and engaged["moe.ffn"] >= 1
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0
    assert engaged["moe.sum.linear"] >= 2 and engaged["moe.sum.product"] == 0   # combine, dispatch
    assert engaged["grouped_matmul.kernel"] > 0
    assert engaged["grouped_matmul.ragged_dot"] == engaged["tp_overlap.plain"] == 0  # fallback_sites
    assert engaged["flash.bwd_fused"] >= 1 and engaged["flash.bwd_split"] == 0
    text, kernels = step.lowered_text, step.lowered_kernels
    flash = sorted(k for k in kernels if not k.startswith("ragged-dot"))
    assert flash == ["dsa.attend"] * 2, kernels   # forward, fused backward
    # the fused backward's own limit: 24 MiB of kv blocks and scratch + 1 of row blocks + 8 spare
    assert _fused_bwd_params(512, 8192, 128, 1, 2).vmem_limit_bytes == 33 << 20
    assert len(re.findall(r"scoped_memory_configs[^}]*size\\22: %d\}" % (33 << 20), text)) == 1
    assert "ragged_dot" not in text   # `lax.ragged_dot`, which compiles to XLA's ragged-dot-none
    assert "ragged-dot-tiled-wgrad" in kernels
    # the selection: packed, a layer's and the stack's; nothing [T, T], whatever its type
    assert "1x8192x256xi32" in text
    assert "tensor<2x1x8192x256xi32>" in train_step(v5e, **KEYE_2).lowered_text
    assert "8192x8192x" not in text
    # the tokens x the held rows: no such matrix; a band's block is 256 tokens and its window
    # 256 x 16384 / 8192 = 512 rows (PR 63: no window of 256 x top-8 rows)
    assert "8192x16384x" not in text
    assert "256x512xi1" in text and "256x2048xi1" not in text
    assert "1x32x8192x128xbf16" in text and "1x4x8192x128xbf16" in text
    assert "16x2048x768x" in text and "128x2048x768x" not in text
    # nothing of the indexer is made again for the backward, and nothing of it is differentiated
    indexer = [n for n in step.lowered_op_names if "dsa.select" in n or "dsa.index" in n]
    assert indexer and not [n for n in indexer if "rematted_computation" in n or "transpose(" in n]


@pytest.mark.parametrize("scope", KEYE_SCOPES)
def test_keye_share_train_step_holds_the_scope_its_readers_sum(v5e, scope):
    """A scope the cell's readers sum is in the LOWERED step (the one lowering
    of the file's other cases of this step: tests/v5e_steps.py's memo), a
    case a scope; that it outlives the compile is the slow case's."""
    assert train_step(v5e, **KEYE).has_scope(scope, lowered=True), scope


@pytest.mark.slow
def test_keye_share_train_step_compiles_with_the_vmem_its_fused_backward_states(v5e):
    """The same one-layer step COMPILED, outside the tier-1 clock (265 CPU s
    where two layers, which the stack scans, cost 443, PR 54): this compile
    is the check that Mosaic accepts the fused backward's block at the 33 MiB
    of VMEM it states; the kernels stand at the lowered module's sites under
    their names; the index scores are at most [1, 16, 512, 8192] float32 a
    chunk; the band's blocks and windows as they are compiled; nothing of
    the indexer is made again for the backward; every scope the cell's
    readers sum outlives the compile."""
    step = train_step(v5e, **KEYE)
    hlo, kernels = step.hlo, step.kernels
    flash = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert flash == ["dsa.attend"] * 2, kernels   # forward, fused backward
    assert len(re.findall(r'"scoped_memory_configs":\[\{[^}]*"size":"%d"' % (33 << 20), hlo)) == 1
    assert "ragged-dot-none" not in hlo
    assert any(k.startswith("ragged-dot-tiled-wgrad") for k in kernels)
    assert re.search(r"s32\[1,8192,256\]", hlo)
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", hlo)
    assert not re.search(r"\[(?:\d+,)*8192,16384\]", hlo)
    assert re.search(r"pred\[256,512\]", hlo) and re.search(r"f32\[256,2048\]", hlo)
    assert not re.search(r"pred\[256,2048\]", hlo) and re.search(r"bf16\[512,2048\]", hlo)
    keys = {int(k) for k in re.findall(r"f32\[(?:1,)?16,512,(\d+)\]", hlo)}   # a chunk's scores
    assert keys and max(keys) == 8192 and min(keys) > 2048
    assert re.search(r"bf16\[1,32,8192,128\]", hlo) and re.search(r"bf16\[1,4,8192,128\]", hlo)
    assert "16,2048,768]" in hlo and "128,2048,768]" not in hlo and "8192,128]" in hlo
    indexer = [n for n in step.op_names if "dsa.select" in n or "dsa.index" in n]
    assert indexer and not [n for n in indexer if "rematted_computation" in n or "transpose(" in n]
    assert not scopes_lost(step, KEYE_SCOPES)
