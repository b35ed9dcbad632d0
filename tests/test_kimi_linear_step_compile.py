"""The step of `kimi-linear-train-8k` for a described v5e (tests/v5e_steps.py),
in a file of its cell's own (PR 45's rule): Kimi-Linear-48B-A3B's layers 1-5 (a
dense KDA layer, then KDA, KDA, NoPE MLA, KDA over top-8 of 256 experts with 8
held and a shared one; ALL 32 heads of both mixers, an eighth of the
vocabulary, 1 x 8192) as the cell builds it. THE LANE READS THE LOWERED MODULE
(PR 68: one lowering for the file, no compile): the text's hash, the
arguments' 6.73 GiB, the kernels by site with the MLA kernels' operands, the
traced sites, every scope the cell's readers sum. What only the compile shows
is ONE case marked `slow`
(`python -m pytest -m slow tests/test_kimi_linear_step_compile.py`, 79 s
alone on this sandbox, PR 68): that the step FITS (arguments + temporaries under the
chip's 15.75 GiB, with the remat policy "dots" keeping what ops/kda.py's
forward kernel writes at 32 heads: 448 MiB a KDA layer), which is also the
guard that ops/flash.py's kernels lower through Mosaic at keys of 192 beside
values of 128 over ONE kv block of 8,192 keys (the fused backward at its own
45.75 MiB of VMEM), ops/kda.py's at 32 heads (a grid four times Solar-Open2's)
and ops/grouped_matmul.py's at K 2304 / N 1024. Every PR's run of the cell on
the chip shows the same (`hbm_step_gib.train`, `hbm_peak_gib.train`, the
step's table by scope)."""

import re

import pytest

from v5e_steps import grouped_kernels, scopes_lost, train_step, v5e  # noqa: F401 - a fixture

KIMI_LINEAR = dict(batch=1, model="kimi-linear-48b-a3b", n_layers=5, seq=8192, vocab_size=20480,
                   experts_held=8)
GIB = 2 ** 30
SCOPES = ("kda.proj", "kda.conv", "kda.gates", "kda.scan", "kda.norm", "kda.out", "mla.down",
          "mla.up", "mla.glue", "mla.attend", "mla.out", "dense.ffn", "moe.router", "moe.dispatch",
          "moe.experts", "moe.combine", "shared.ffn", "block.norm", "block.stack", "embed", "head",
          "optim")


# sha256 of the lowered step of kimi-linear-48b-a3b as `kimi-linear-train-8k` builds it, as PR 64
# lowers it and as every run of PR 64 on the chip ran it (the rehearsal before call 1 and the
# tree after the last call hash alike). A change that MEANS to move the step replaces the hash
# and says what moved.
# Replaced ON PURPOSE by PR 65: ops/kda.py's kernels take the constant 0 / 1 matrix of the sums as
# bfloat16 [1024, 128] where float32 stood, and `kda_bwd` its transpose [128, 1024] as one more
# operand (three bf16 passes a sum where `highest` spent six); the kernels' own bodies, which
# are where the rows of the inverse and of the short levels went, are not in the hash
# (f45b27c5... from PR 64)
_KIMI_LINEAR_STEP = "0a6cdf99c7966c9ad0018c4922fde82da02a5c64e69f293ba628b10deae56a9f"


def test_kimi_linear_lowered_step_is_the_one_the_chip_ran(v5e):
    assert train_step(v5e, **KIMI_LINEAR).lowered_hash() == _KIMI_LINEAR_STEP


KERNELS = (["gdn_conv_bwd"] * 12 + ["gdn_conv_fwd"] * 24 + ["kda_bwd"] * 4 + ["kda_fwd"] * 4
           + ["mla.attend"] * 2)


def test_kimi_linear_train_step_fits_the_chip(v5e):
    """602,450,816 parameters x 12 B = 6.73 GiB of arguments, summed from the
    step's abstract inputs: over a quarter of the chip by the arguments
    alone, the benchmark's floor. That the temporaries fit beside them is the
    slow case's, and `hbm_peak_gib.train`'s on the chip."""
    arguments = train_step(v5e, **KIMI_LINEAR).argument_bytes
    assert 6.72 * GIB < arguments < 6.75 * GIB
    assert arguments > 0.25 * 16 * GIB


@pytest.mark.parametrize("scope", SCOPES)
def test_kimi_linear_train_step_has_every_scope_its_readers_sum(v5e, scope):
    assert train_step(v5e, **KIMI_LINEAR).has_scope(scope, lowered=True)


def test_kimi_linear_train_step_runs_its_kernels_and_counts_its_sites(v5e):
    """The Pallas kernels of the LOWERED step, a site each: the MLA layer's
    flash forward and its FUSED backward at 32 heads, keys of 192 and values
    of 128 over ONE kv block of 8,192 keys, named after their scope (no value
    padded to 192: dv is [1, 32, 8192, 128]); `gdn_conv_fwd` / `gdn_conv_bwd`
    under `kda.conv` (q, k and v of each of FOUR KDA layers forward, forward
    again in the backward and backward); `kda_fwd` x 4 and `kda_bwd` x 4
    under `kda.scan` at [1, 32, 8192, 128]; the grouped matmuls of four
    expert layers and no `lax.ragged_dot`."""
    step = train_step(v5e, **KIMI_LINEAR)
    engaged = step.engaged("kda.attn", "mla.attn", "kda.rule", "kda.kernel", "gdn_conv.kernel",
                           "moe.compact", "moe.full", "flash.bwd_fused", "flash.bwd_split",
                           "grouped_matmul.ragged_dot", "grouped_matmul.kernel")
    assert engaged["kda.attn"] >= 4 and engaged["kda.kernel"] >= 4 and engaged["mla.attn"] >= 1
    assert engaged["gdn_conv.kernel"] >= 12 and engaged["moe.compact"] >= 4
    assert engaged["flash.bwd_fused"] == 1 and engaged["grouped_matmul.kernel"] > 0
    assert engaged["moe.full"] == engaged["flash.bwd_split"] == 0
    assert engaged["grouped_matmul.ragged_dot"] == 0   # fallback_sites
    text, kernels = step.lowered_text, step.lowered_kernels
    names = sorted(k for k in kernels if not k.startswith("ragged-dot"))
    assert names == KERNELS, names
    grouped = grouped_kernels(kernels)
    assert grouped and all(k.startswith("ragged-dot-tiled") for k in grouped), grouped
    assert "ragged_dot" not in text   # `lax.ragged_dot`, which compiles to XLA's ragged-dot-none
    # the kernels' own operands: keys of 192, values of 128, and nothing of a value at 192
    attend = [line for line in text.splitlines() if "@tpu_custom_call(" in line
              and "1x32x8192x192xbf16" in line]
    assert len(attend) == 2 and all("1x32x8192x128xbf16" in line for line in attend)
    assert "8192x8192x" not in text


@pytest.mark.slow
def test_kimi_linear_train_step_compiles_for_the_chip_and_fits_it(v5e):
    """The step COMPILED, outside the tier-1 clock: the arguments are what
    the abstract inputs sum to; the temporaries with `kda_out` and
    `kda_states` of four KDA layers at 32 heads saved: under the chip's 15.75
    GiB (the rehearsal of ISSUE 64's step 3; the configuration file's
    `reduced` has the table). The kernels Mosaic took stand at the lowered
    module's sites under their names, the MLA kernels with keys of 192 beside
    values of 128; no `ragged-dot-none`; nothing [8192, 8192]; every scope
    outlives the compile."""
    step = train_step(v5e, **KIMI_LINEAR)
    memory = step.memory
    assert 6.72 * GIB < memory.argument_size_in_bytes < 6.75 * GIB
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0 * GIB < 15.75 * GIB
    hlo, kernels = step.hlo, step.kernels
    names = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert names == KERNELS, names
    grouped = grouped_kernels(kernels)
    assert grouped and all(k.startswith("ragged-dot-tiled") for k in grouped), grouped
    assert "ragged-dot-none" not in hlo
    attend = [line for line in hlo.splitlines() if "tpu_custom_call" in line
              and re.search(r'op_name="[^"]*mla\.attend', line)]
    assert len(attend) == 2
    # the kernels' own operands: keys of 192, values of 128, and nothing of a value at 192
    assert all("bf16[1,32,8192,192]" in line and "bf16[1,32,8192,128]" in line for line in attend)
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", hlo)
    assert not scopes_lost(step, SCOPES)
