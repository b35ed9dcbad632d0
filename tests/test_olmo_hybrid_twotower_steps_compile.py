"""The steps of `olmo-hybrid-train` and `twotower-train-8k` for a described
v5e (tests/v5e_steps.py). Olmo-Hybrid-7B's one period (three
gated-delta-rule linear layers and a full one over a SwiGLU of 11008, an
eighth of the vocabulary held, 1 x 4096) as the cell builds it. The causal
tower of Nemotron-Labs-TwoTower-30B-A3B's first nine layers (`MEMEM*EME`:
four Mamba-2 mixers, four expert layers of relu^2 experts with 8 of 128
held, one GQA 32 / 2 attention layer; an eighth of the vocabulary, 1 x
8192) as the cell builds it. THE LANE READS THE LOWERED MODULES (PR 68: one
lowering a step, no compile): the hashes, the arguments' bytes, the kernels
by site, the traced sites, the shapes, every scope the cells' readers sum.
What only a compile shows is ONE case a step marked `slow`
(`python -m pytest -m slow tests/test_olmo_hybrid_twotower_steps_compile.py`:
38 and 59 s alone on this sandbox, PR 68):
that each step FITS (arguments + temporaries under the chip's 15.75 GiB),
which is also the guard that ops/gated_delta.py's and ops/gdn_conv.py's
kernels lower through Mosaic at heads of 96 / 192, ops/gdn_conv.py's WITH a
bias at 48 heads of 128, ops/grouped_matmul.py's at an expert width of
1,856 (14.5 lane tiles, taken whole), and that ops/ssd.py's two kernels
stand in the step where the convolution's leave their arrays, and
ops/gated_norm.py's two where the scan's leave theirs: no copy, transpose
or loop of XLA's own between them. Every PR's run of the two cells on the
chip shows the same (`hbm_step_gib.train`, `hbm_peak_gib.train`, the step's
table by scope)."""

import re

import pytest

from v5e_steps import grouped_kernels, scopes_lost, train_step, v5e  # noqa: F401 - a fixture

OLMO_HYBRID = dict(batch=1, model="olmo-hybrid-7b", n_layers=4, vocab_size=12544)
# sha256 of the lowered step of olmo-hybrid-7b as `olmo-hybrid-train` builds it, as PR 48
# lowers it: the rule as two `pallas_call`s a layer (PR 47) and the convolution, SiLU and L2
# norms before it as two a tensor (the account of every hash is
# tests/test_m7b_steps_compile.py's; the kernels' own bodies are not in it)
# PR 65 changed `gated_delta_fwd`'s BODY alone (`_inverses` multiplies the later halves' rows
# from blocks of 8 positions up; the same bits out): its operands and shapes, which is what the
# lowered text holds of a kernel, are the parent's, so the hash stands unedited
_OLMO_HYBRID_STEP = "68b139dadb3f7426e556122e7adf2d0c859bcfe78b76edb72e3d609a81dcf6f8"
TWOTOWER = dict(batch=1, model="nemotron-twotower-30b-a3b", n_layers=9, seq=8192,
                vocab_size=16384, experts_held=8)
# sha256 of the lowered step as `twotower-train-8k` builds it, as PR 52 lowers it: the Mamba
# mixers' gated group norm as ops/gated_norm.py's two kernels where five lines of jax.numpy
# stood (the account of every hash is tests/test_m7b_steps_compile.py's; the kernels' own
# bodies are not in it)
# Replaced ON PURPOSE by PR 59: the chosen experts' scores are picked by a compare and a sum
# (`moe._of_chosen`) where `take_along_axis` gathered them one by one (74134453... from PR 52)
# Replaced ON PURPOSE by PR 63: the band that sums the held rows into their tokens takes a
# window of 256 x C / N rows a block (256 where 1,536 stood) and as many windows as a block's run is
# long (`moe._sum_by_band`: a `fori_loop` inside `lax.map`), rows past the held pairs name no
# token (`moe._held_rows`), and the layer's statistics carry `band_trips` (4d64810d... from PR 59)
_TWOTOWER_STEP = "3d7cf20932955b265f7c9275051d7df28c80af6906ed3bc5884d05a7d794086d"
GIB = 2 ** 30
OLMO_HYBRID_SCOPES = (
    "gdn.proj", "gdn.conv", "gdn.gates", "gdn.scan", "gdn.norm", "gdn.out", "attn.qkv",
    "attn.rope", "attn.attend", "attn.out", "dense.ffn", "block.norm", "block.stack", "embed",
    "head", "optim")
TWOTOWER_SCOPES = (
    "ssm.proj", "ssm.conv", "ssm.gates", "ssm.scan", "ssm.norm", "ssm.out", "attn.qkv",
    "attn.attend", "attn.out", "moe.router", "moe.dispatch", "moe.experts", "moe.combine",
    "shared.ffn", "block.norm", "block.stack", "embed", "head", "optim")


def test_olmo_hybrid_train_step_lowers_to_the_text_it_had(v5e):
    assert train_step(v5e, **OLMO_HYBRID).lowered_hash() == _OLMO_HYBRID_STEP


OLMO_HYBRID_KERNELS = (["attn.attend"] * 2 + ["gated_delta_bwd"] * 3 + ["gated_delta_fwd"] * 3
                       + ["gdn_conv_bwd"] * 9 + ["gdn_conv_fwd"] * 18)


def test_olmo_hybrid_train_step_fits_the_chip_and_runs_the_rule_in_kernels(v5e):
    """The step with the rule (PR 47) and the convolution, SiLU and L2
    norms before it (PR 48) as Pallas kernels, LOWERED for the described
    chip at heads of 96 / 192: 10.38 GiB of arguments (928.9M parameters
    x 12 B), summed from the step's abstract inputs; the Pallas
    kernels are the full layer's flash forward and its fused backward at
    30 / 30 heads of 128, named after their scope; SIX under `gdn.scan`,
    named after ops/gated_delta.py's two jitted functions: each linear
    layer's forward and its backward, and no forward a second time,
    because the policy saves o, the chunks' starting states
    ([30, 64, 96, 192] float32 a layer) and the pairs' inverses
    ([30, 32, 64, 128]: a pair's two diagonal blocks side by side, the
    zeros off the diagonal not kept) by name; and TWENTY-SEVEN under
    `gdn.conv`, named after ops/gdn_conv.py's two: q, k and v of each
    linear layer forward, forward AGAIN in the backward (nothing of the
    chain is saved but the bfloat16 projection) and backward. No chunked
    array [64, 1, 30, 64, ...] and no triangular solve; no array is
    [4096, 4096]; the sublayer and the rule count their sites. That the
    temporaries fit beside the arguments, and what XLA leaves under the
    scopes, are the slow case's."""
    step = train_step(v5e, **OLMO_HYBRID)
    engaged = step.engaged("gdn.attn", "gated_delta.kernel", "gdn_conv.kernel", "flash.bwd_fused",
                           "flash.bwd_split", "tp_overlap.plain", "grouped_matmul.ragged_dot")
    assert engaged["gdn.attn"] >= 3 and engaged["gated_delta.kernel"] >= 3
    assert engaged["gdn_conv.kernel"] >= 9          # q, k and v of each linear layer
    assert engaged["flash.bwd_fused"] == 1
    assert engaged["flash.bwd_split"] == engaged["tp_overlap.plain"] == 0   # fallback_sites
    assert engaged["grouped_matmul.ragged_dot"] == 0
    assert 10.3 * GIB < step.argument_bytes < 10.39 * GIB
    text = step.lowered_text
    # named after the scope they stand in, or after the jitted function that holds them
    assert sorted(step.lowered_kernels) == OLMO_HYBRID_KERNELS, step.lowered_kernels
    assert "1x30x4096x128xbf16" in text
    # the states and the inverses, out of the forward and into the backward
    assert "30x64x96x192xf32" in text and "30x32x64x128xf32" in text
    assert "64x1x30x64x" not in text and "triangular_solve" not in text
    assert "4096x1x30x" not in text and "4096x4096x" not in text


@pytest.mark.slow
def test_olmo_hybrid_train_step_compiles_for_the_chip_and_fits_it(v5e):
    """The step COMPILED, outside the tier-1 clock, through Mosaic at heads
    of 96 / 192: with the remat policy "dots" as it is the step is 10.38 GiB
    of arguments + 3.89 of temporaries (4.28 with the jax.numpy convolution,
    4.70 with the jax.numpy scan too), inside the chip's 15.75; the kernels
    stand at the lowered module's sites under their names; of the six under
    `gdn.scan` three are the backward's and none a second forward; of the
    twenty-seven under `gdn.conv` nine are the forward made again and
    eighteen run in the backward, with no float32 pass of XLA's own over a
    [1, 30, 4096, d] array left under that scope. No `while` is left in the
    step (the walk over the chunks is the kernels' grid); q, k and v of the
    full layer meet no copy or transpose; every scope the cell's readers sum
    outlives the compile."""
    step = train_step(v5e, **OLMO_HYBRID)
    assert step.memory.argument_size_in_bytes < 10.39 * GIB
    assert step.memory.temp_size_in_bytes < 4.00 * GIB
    assert (step.memory.argument_size_in_bytes + step.memory.temp_size_in_bytes) < 15.75 * GIB
    hlo, kernels = step.hlo, step.kernels
    assert sorted(re.sub(r"\.\d+$", "", k) for k in kernels) == OLMO_HYBRID_KERNELS, kernels
    assert re.search(r"bf16\[1,30,4096,128\]", hlo)
    rule = [line for line in hlo.splitlines()
            if "tpu_custom_call" in line and re.search(r'op_name="[^"]*gdn\.scan', line)]
    assert len(rule) == 6 and sum("transpose(" in line for line in rule) == 3
    assert not any("rematted_computation" in line for line in rule)   # no second forward
    # the states and the inverses, out of the forward and into the backward
    assert all("f32[30,64,96,192]" in line and "f32[30,32,64,128]" in line for line in rule)
    under_conv = [line for line in hlo.splitlines() if re.search(r'op_name="[^"]*gdn\.conv', line)]
    conv = [line for line in under_conv if "tpu_custom_call" in line]
    # forward; in the backward the forward again and the transpose: the scope on all three
    assert len(conv) == 27 and sum("transpose(" in line for line in conv) == 18
    assert sum("rematted_computation" in line for line in conv) == 9
    # bfloat16 in and float32 out forward, bfloat16 out backward: what the rule's kernels read
    assert sum(bool(re.match(r"\s*%[\w.\-]+ = f32\[1,30,4096,(96|192)\]", line)) for line in conv) == 18
    # what XLA still does under the scope is small: the taps' [30, 4, 8, d] partial sums and
    # their transposes, no pass over the positions
    assert not [line for line in under_conv if line not in conv and re.search(
        r"= (f32|bf16)\[1,30,4096,\d+\]\S* (?!get-tuple-element|bitcast)[\w\-]+\(", line)]
    loops = re.findall(r'= (\([^\n]*?\)) while\([^\n]*op_name="([^"]*)"', hlo)
    assert not loops, [name for _, name in loops]
    assert "f32[64,1,30,64," not in hlo and "triangular" not in hlo.lower()
    # nothing is stacked over the 4,096 positions: no loop walks them one at a time
    assert not re.search(r"\[4096,1,30,", hlo)
    assert not re.search(r"\[(?:\d+,)*4096,4096\]", hlo)
    # head-major from the projections to `wo` in the full layer: no copy or transpose of q, k, v
    moved = [shape for shape, op in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose)\(", hlo, re.M)
        if re.search(r"bf16\[1,30,4096,128\]|bf16\[1,4096,30,128\]", shape)]
    assert not moved, moved
    assert not scopes_lost(step, OLMO_HYBRID_SCOPES)


def test_twotower_train_step_lowers_to_the_text_it_had(v5e):
    assert train_step(v5e, **TWOTOWER).lowered_hash() == _TWOTOWER_STEP


TWOTOWER_KERNELS = (["attn.attend"] * 2 + ["gated_norm_bwd"] * 3 + ["gated_norm_fwd"] * 6
                    + ["gdn_conv_bwd"] * 3 + ["gdn_conv_fwd"] * 6
                    + ["ssd_scan_bwd"] * 3 + ["ssd_scan_fwd"] * 3)


def test_twotower_train_step_fits_the_chip_with_its_kernels_scopes_and_sites(v5e):
    """The step LOWERED for the described chip: 7.45 GiB of arguments
    (666,963,456 parameters x 12 B), summed from the step's abstract
    inputs. The
    Pallas kernels: the attention layer's flash forward and its fused
    backward at 32 / 2 heads of 128, named after their scope; the
    convolution's `gdn_conv_fwd` / `gdn_conv_bwd` under `ssm.conv` (a Mamba
    layer's forward, its forward again in the backward, its transpose: the
    pair of layers the stack scans counted once a body); the scan's
    `ssd_scan_fwd` / `ssd_scan_bwd` under `ssm.scan`, ONE forward and ONE
    backward a body and no forward a second time; the gated norm's
    `gated_norm_fwd` / `gated_norm_bwd` under `ssm.norm` (forward, forward
    again under the block's checkpoint, backward); the expert layers'
    grouped matmuls, every one a `ragged-dot-tiled*` of
    ops/grouped_matmul.py and none `lax.ragged_dot`, with no `w_gate`: an
    expert is two matrices. Nothing is [8192, 8192]; no rotary; each
    sublayer counts its site. That the temporaries fit beside the
    arguments, and what XLA leaves between the kernels, are the slow
    case's."""
    step = train_step(v5e, **TWOTOWER)
    engaged = step.engaged("ssm.mixer", "gdn_conv.kernel", "ssd_scan.kernel", "gated_norm.kernel",
                           "moe.ffn", "moe.compact", "moe.full", "moe.sum.linear",
                           "flash.bwd_fused", "flash.bwd_split",
                           "tp_overlap.plain", "grouped_matmul.ragged_dot", "grouped_matmul.kernel")
    assert engaged["ssm.mixer"] >= 1 and engaged["gdn_conv.kernel"] >= 1 and engaged["moe.ffn"] >= 1
    assert engaged["ssd_scan.kernel"] >= 1 and engaged["gated_norm.kernel"] >= 1
    assert engaged["moe.compact"] >= 1 and engaged["moe.full"] == 0 and engaged["moe.sum.linear"] >= 1
    assert engaged["flash.bwd_fused"] == 1 and engaged["grouped_matmul.kernel"] >= 6
    assert engaged["flash.bwd_split"] == engaged["tp_overlap.plain"] == 0   # fallback_sites
    assert engaged["grouped_matmul.ragged_dot"] == 0
    assert 7.4 * GIB < step.argument_bytes < 7.46 * GIB
    text, kernels = step.lowered_text, step.lowered_kernels
    names = sorted(k for k in kernels if not k.startswith("ragged-dot"))
    assert names == TWOTOWER_KERNELS, names
    grouped = set(grouped_kernels(kernels))
    assert grouped == {"ragged-dot-tiled", "ragged-dot-tiled-dgrad", "ragged-dot-tiled-wgrad"}
    assert "ragged_dot" not in text and "w_gate" not in text
    assert "1x32x8192x128xbf16" in text and "1x2x8192x128xbf16" in text
    assert "1x48x8192x128xf32" in text and "1x8192x4096xf32" in text
    assert "8192x8192x" not in text
    assert not step.has_scope("attn.rope", lowered=True)   # no rotary


@pytest.mark.slow
def test_twotower_train_step_compiles_for_the_chip_and_fits_it(v5e):
    """The step COMPILED, outside the tier-1 clock. With the remat policy
    "dots" and what ops/ssd.py's forward kernel writes saved by name
    (`ssd_out`, `ssd_states`: 128 + 128 MiB a Mamba layer) the step is 7.45
    GiB of arguments + 7.65 of temporaries, inside the chip's 15.75 (7.70
    before PR 52, 6.82 before PR 50, when nothing of the scan was kept and
    its masks were temporaries). The kernels stand at the lowered module's
    sites under their names: the convolution's nine under `ssm.conv` at
    [.., 48, 8192, 128]; the scan's six under `ssm.scan`, reading the
    convolution's [1, 48, 8192, 128] where it stands and writing dx, dB and
    dC into one array of that shape; the gated norm's nine under `ssm.norm`
    on y and z [1, 8192, 4096] as they stand: nothing else under that scope
    touches an array of 8,192 rows (no copy, transpose or reshape to (8
    groups, 512)), and the backward's dy is `ssd_scan_bwd`'s operand itself;
    none of the grouped matmuls XLA's own `ragged-dot-none`. The loops left
    are the stack's scan over (`ME` x 2), forward and backward, and the
    experts' bands': none walks the 64 chunks, no [.., 128, 128] float32
    mask and no chunked state is an array of the step, no copy or transpose
    of x stands under `ssm.scan`; nothing is [8192, 8192]; q, k and v go
    head-major from the projections to `wo` with no copy or transpose;
    every scope the cell's readers sum outlives the compile."""
    step = train_step(v5e, **TWOTOWER)
    assert step.memory.argument_size_in_bytes < 7.46 * GIB
    assert step.memory.temp_size_in_bytes < 7.8 * GIB
    assert (step.memory.argument_size_in_bytes + step.memory.temp_size_in_bytes) < 15.75 * GIB
    hlo, kernels = step.hlo, step.kernels
    names = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert names == TWOTOWER_KERNELS, names
    grouped = set(grouped_kernels(kernels))
    assert grouped == {"ragged-dot-tiled", "ragged-dot-tiled-dgrad", "ragged-dot-tiled-wgrad"}
    assert "ragged-dot-none" not in hlo and "w_gate" not in step.lowered_text
    conv = [line for line in hlo.splitlines()
            if "tpu_custom_call" in line and re.search(r'op_name="[^"]*ssm\.conv', line)]
    assert len(conv) == 9 and all("48,8192,128" in line for line in conv)
    assert re.search(r"bf16\[1,32,8192,128\]", hlo) and re.search(r"bf16\[1,2,8192,128\]", hlo)
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", hlo)
    scan = [line for line in hlo.splitlines()
            if "tpu_custom_call" in line and re.search(r'op_name="[^"]*ssm\.scan', line)]
    assert len(scan) == 6 and all("f32[1,48,8192,128]" in line for line in scan)
    # no loop over the 64 chunks (the jax.numpy form's carried [1, 8, 8, 64, 128] and its 64
    # stacked states), no mask or chunked array of it, in HBM
    assert not re.search(r"f32\[1,8,8,64,128\]|f32\[64,1,8,8,64,128\]", hlo)
    assert not re.search(r"f32\[(?:\d+,){2,}128,128\]", hlo)
    assert not [line for line in hlo.splitlines() if " while(" in line and "ssm." in line]
    in_scan = [(shape, op) for shape, op, rest in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose|concatenate|pad)\((.*)$", hlo, re.M)
        if "ssm.scan" in rest
        and re.search(r"f32\[1,(64,8192,64|32,8192,128|48,8192,128)\]", shape)]
    assert not in_scan, in_scan
    # under `ssm.norm` an array of 8,192 rows is a kernel's operand or output and nothing else's:
    # XLA's form of the norm split the lanes into (8 groups, 512) and copied what it was given
    assert "f32[1024,8,8,512]" not in hlo
    in_norm = [(shape, op) for shape, op, rest in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\((.*)$", hlo, re.M)
        if re.search(r'op_name="[^"]*ssm\.norm', rest) and "8192" in shape
        and op not in ("custom-call", "get-tuple-element")]
    assert not in_norm, in_norm
    norm = [line for line in hlo.splitlines()
            if "tpu_custom_call" in line and re.search(r'op_name="[^"]*ssm\.norm', line)]
    assert len(norm) == 9 and all("f32[1,8192,4096]" in line for line in norm)
    # the backward's dy goes to the scan's backward kernel as it is written: the operand IS the
    # kernel's first output
    dys = re.findall(r"%ssd_scan_bwd[\w.]* = [^\n]*custom-call\([^)]*?(%[\w.\-]+)\), custom_call_target",
                     hlo)
    assert len(dys) == 3
    for dy in dys:
        assert re.search(re.escape(dy) + r" = f32\[1,8192,4096\]\S* get-tuple-element\(%gated_norm_bwd"
                         r"[\w.]*\), index=0", hlo), dy
    assert not step.has_scope("attn.rope")   # no rotary
    moved = [shape for shape, op in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose)\(", hlo, re.M)
        if re.search(r"bf16\[1,(32|2),8192,128\]|bf16\[1,8192,(32|2),128\]", shape)]
    assert not moved, moved
    assert not scopes_lost(step, TWOTOWER_SCOPES)


@pytest.mark.parametrize("scope", OLMO_HYBRID_SCOPES)
def test_olmo_hybrid_train_step_holds_the_scope_its_readers_sum(v5e, scope):
    """A scope the cell's readers sum is in the LOWERED step (the one lowering
    of the file's other cases of this step: tests/v5e_steps.py's memo), a
    case a scope; that it outlives the compile is the slow case's."""
    assert train_step(v5e, **OLMO_HYBRID).has_scope(scope, lowered=True), scope


@pytest.mark.parametrize("scope", TWOTOWER_SCOPES)
def test_twotower_train_step_holds_the_scope_its_readers_sum(v5e, scope):
    """A scope the cell's readers sum is in the LOWERED step (the one lowering
    of the file's other cases of this step: tests/v5e_steps.py's memo), a
    case a scope; that it outlives the compile is the slow case's."""
    assert train_step(v5e, **TWOTOWER).has_scope(scope, lowered=True), scope
