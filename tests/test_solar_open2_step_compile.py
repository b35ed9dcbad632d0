"""The step of `solar-open2-train-8k` for a described v5e (tests/v5e_steps.py),
in a file of its cell's own (PR 45's rule): Solar-Open2-250B's one period (a
gated NoPE GQA layer and three Kimi-Delta-Attention layers at 8 of 64 heads,
each over top-8 of 320 experts with 8 held and a shared one; an eighth of the
vocabulary, 1 x 8192) as the cell builds it. THE LANE READS THE LOWERED MODULE
(PR 68: one lowering for the file, no compile): the text's hash, the
arguments' 9.39 GiB, the kernels by site and scope, the traced sites, the
band's loops as they are traced, every scope the cell's readers sum. What
only the compile shows is ONE case marked `slow`
(`python -m pytest -m slow tests/test_solar_open2_step_compile.py`, 65 s alone
on this sandbox, PR 68): that the step FITS (arguments + temporaries under the chip's
15.75 GiB, with the remat policy "dots" keeping what ops/kda.py's forward
kernel writes: o, the chunks' starting states and the pairs' inverses, 112
MiB a KDA layer), which is also the guard that ops/kda.py's two kernels and
ops/gdn_conv.py's lower through Mosaic at 8 heads of 128 and
ops/grouped_matmul.py's at K 4096 / N 1280 (ten lane tiles: `pick_tiles`
takes it as it is). Every PR's run of the cell on the chip shows the same
(`hbm_step_gib.train`, `hbm_peak_gib.train`, the step's table by scope)."""

import re

import pytest

from v5e_steps import grouped_kernels, scopes_lost, train_step, v5e  # noqa: F401 - a fixture

SOLAR_OPEN2 = dict(batch=1, model="solar-open2-250b", n_layers=4, seq=8192, vocab_size=24576,
                   experts_held=8, n_heads=8, n_kv_heads=1, kda_heads=8)
GIB = 2 ** 30
SCOPES = ("kda.proj", "kda.conv", "kda.gates", "kda.scan", "kda.norm", "kda.out", "attn.qkv",
          "attn.attend", "attn.gate", "attn.out", "moe.router", "moe.dispatch", "moe.experts",
          "moe.combine", "shared.ffn", "block.norm", "block.stack", "embed", "head", "optim")


KERNELS = (["attn.attend"] * 2 + ["gdn_conv_bwd"] * 9 + ["gdn_conv_fwd"] * 18 + ["kda_bwd"] * 3
           + ["kda_fwd"] * 3)


def test_solar_open2_train_step_fits_the_chip_with_what_the_rules_forward_hands_on_kept(v5e):
    """840,226,112 parameters x 12 B = 9.39 GiB of arguments, summed from the
    step's abstract inputs. What the temporaries are with `kda_out` and
    `kda_states` saved is the slow case's, and `hbm_peak_gib.train`'s on the
    chip."""
    arguments = train_step(v5e, **SOLAR_OPEN2).argument_bytes
    assert 9.38 * GIB < arguments < 9.41 * GIB
    assert arguments > 0.25 * 16 * GIB   # the benchmark's floor, by the arguments alone


# sha256 of the lowered step of solar-open2-250b as `solar-open2-train-8k` builds it, as PR 63's
# tree (the parent of PR 64) lowers it: PR 64 gave the KDA sublayer an option (`kda_neg_eigval`:
# beta doubled or not) and ops/flash.py a value width of its own, and neither is work in this
# step. A change that MEANS to move the step replaces the hash and says what moved.
# Replaced ON PURPOSE by PR 65: ops/kda.py's kernels take the constant 0 / 1 matrix of the sums as
# bfloat16 [1024, 128] where float32 stood, and `kda_bwd` its transpose [128, 1024] as one more
# operand (three bf16 passes a sum where `highest` spent six); the kernels' own bodies are not
# in the hash (b5b5635a... from PR 63)
_SOLAR_OPEN2_STEP = "c95f2a04b352a7a9c75ba96d8971c09cf980f7374f8f989c2a7d75a8c26c9f6c"


def test_solar_open2_lowered_step_is_text_for_text_the_parents(v5e):
    assert train_step(v5e, **SOLAR_OPEN2).lowered_hash() == _SOLAR_OPEN2_STEP


@pytest.mark.parametrize("scope", SCOPES)
def test_solar_open2_train_step_has_every_scope_its_readers_sum(v5e, scope):
    assert train_step(v5e, **SOLAR_OPEN2).has_scope(scope, lowered=True)


def test_solar_open2_train_step_runs_its_kernels_and_counts_its_sites(v5e):
    """The Pallas kernels of the LOWERED step, a site each: the GQA layer's
    flash forward and its fused backward at 8 / 1 heads of 128 over ONE kv
    block of 8,192 keys, named after their scope; `gdn_conv_fwd` /
    `gdn_conv_bwd` under `kda.conv`: q, k and v of each KDA layer forward,
    forward AGAIN in the backward (nothing of the chain is saved but the
    bfloat16 projection) and backward; `kda_fwd` x 3 and `kda_bwd` x 3 under
    `kda.scan`, the rule twice a layer and no forward again (the remat policy
    keeps what the forward kernel writes); the grouped matmuls of four expert
    layers and no `lax.ragged_dot`. Nothing is left of the jax.numpy rule: no
    loop under `kda.scan`, no triangular solve, and no array is [8192, 8192]."""
    step = train_step(v5e, **SOLAR_OPEN2)
    engaged = step.engaged("kda.attn", "kda.rule", "kda.kernel", "gdn_conv.kernel", "moe.compact",
                           "moe.full", "flash.bwd_fused", "flash.bwd_split",
                           "grouped_matmul.ragged_dot", "grouped_matmul.kernel")
    assert engaged["kda.attn"] >= 3 and engaged["kda.rule"] >= 3 and engaged["kda.kernel"] >= 3
    assert engaged["gdn_conv.kernel"] >= 9 and engaged["moe.compact"] >= 4
    assert engaged["flash.bwd_fused"] == 1 and engaged["grouped_matmul.kernel"] > 0
    assert engaged["moe.full"] == engaged["flash.bwd_split"] == 0
    assert engaged["grouped_matmul.ragged_dot"] == 0   # fallback_sites
    text, kernels = step.lowered_text, step.lowered_kernels
    names = sorted(k for k in kernels if not k.startswith("ragged-dot"))
    assert names == KERNELS, names
    grouped = grouped_kernels(kernels)
    assert grouped and all(k.startswith("ragged-dot-tiled") for k in grouped), grouped
    assert "ragged_dot" not in text   # `lax.ragged_dot`, which compiles to XLA's ragged-dot-none
    loops = [n for n in step.lowered_op_names if n.endswith("/while")]
    assert loops and all("block.stack" in n or "moe." in n for n in loops), loops
    assert not [n for n in step.lowered_op_names if "kda." in n and "/while" in n]
    assert "triangular_solve" not in text and "8192x8192x" not in text
    assert "1x8x8192x128xbf16" in text


def test_solar_open2_train_step_sums_the_held_rows_by_windows_of_a_blocks_run(v5e):
    """PR 63: each of the four expert layers' two sums of the 6,656 held rows into the 8,192
    tokens (the combine's forward, the dispatch's backward) is the band whose window is 256 x
    6656 / 8192 -> 256 rows a block of 256 tokens, where 256 x top-8 = 2,048 stood (a block
    owns 51 on average): a [256, 256] 0/1 matrix a window and no [256, 2048] one, and under
    each sum's scope a loop over the blocks whose body calls the loop over a block's windows,
    as the step is traced; neither the tokens x the held rows nor a product of them anywhere. That
    the compiled step keeps eight of each loop is the slow case's."""
    step = train_step(v5e, **SOLAR_OPEN2)
    engaged = step.engaged("moe.sum.linear", "moe.sum.product")
    assert engaged["moe.sum.linear"] >= 2 and engaged["moe.sum.product"] == 0
    text = step.lowered_text
    assert "256x256xi1" in text and "256x2048xi1" not in text and "8192x6656x" not in text
    names = step.lowered_op_names
    for scope in ("moe.combine/moe.held", "moe.dispatch/moe.held"):
        assert any(n.endswith(scope + "/while") for n in names), scope               # the blocks
        assert any(n.endswith(scope + "/while/body/closed_call") for n in names), scope
    # a block's windows: the loop inside the function the blocks' loop calls, a product a window
    assert "while/body/dot_general" in names


@pytest.mark.slow
def test_solar_open2_train_step_compiles_for_the_chip_and_fits_it(v5e):
    """The step COMPILED, outside the tier-1 clock: the arguments are what
    the abstract inputs sum to; the temporaries with `kda_out` and
    `kda_states` saved (336 MiB for the three layers) where the jax.numpy
    form's chunk arrays stood (4.87 GiB then; rehearsal, PR 60): under the
    chip's 15.75 with room, which is what let `REMAT_SAVES` name them (ISSUE
    61, tentpole 3: the memory decides). The kernels Mosaic took stand at the
    lowered module's sites under their names and scopes, the backward's as
    transposes; the loops left are the stack's and the experts' bands', eight
    over the blocks and eight over a block's windows (PR 63); no triangular
    solve, nothing [8192, 8192]; every scope outlives the compile."""
    step = train_step(v5e, **SOLAR_OPEN2)
    memory = step.memory
    assert 9.38 * GIB < memory.argument_size_in_bytes < 9.41 * GIB
    assert memory.temp_size_in_bytes < 5.0 * GIB
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5 * GIB < 15.75 * GIB
    hlo, kernels = step.hlo, step.kernels
    names = sorted(re.sub(r"\.\d+$", "", k) for k in kernels if not k.startswith("ragged-dot"))
    assert names == KERNELS, names
    grouped = grouped_kernels(kernels)
    assert grouped and all(k.startswith("ragged-dot-tiled") for k in grouped), grouped
    conv, scan = ([line for line in hlo.splitlines() if "tpu_custom_call" in line
                   and re.search(rf'op_name="[^"]*kda\.{scope}', line)] for scope in ("conv", "scan"))
    assert len(conv) == 27 and sum("transpose(" in line for line in conv) == 18
    assert len(scan) == 6 and sum("transpose(" in line for line in scan) == 3
    loops = re.findall(r'= (\([^\n]*?\)) while\([^\n]*op_name="([^"]*)"', hlo)
    assert loops and all("block.stack" in name or "moe." in name for _, name in loops), \
        [name for _, name in loops]
    assert not any("kda.scan" in name for _, name in loops)
    assert "triangular-solve" not in hlo and "TriangularSolve" not in hlo
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", hlo)
    assert re.search(r"bf16\[1,8,8192,128\]", hlo)
    # the band of PR 63, as it is compiled
    assert re.search(r"pred\[256,256\]", hlo) and not re.search(r"pred\[256,2048\]", hlo)
    assert not re.search(r"\[(?:\d+,)*8192,6656\]", hlo)
    held = [name for _, name in loops if "moe.held" in name]
    over_windows = [name for name in held if name.endswith("moe.held/while/body/closed_call/while")]
    over_blocks = [name for name in held if name.endswith("moe.held/while")]
    assert len(over_windows) == len(over_blocks) == 8 and len(held) == 16, held
    for scope in ("moe.combine/moe.held", "moe.dispatch/moe.held"):
        assert sum(scope in name for name in over_windows) == 4, (scope, over_windows)
    assert not scopes_lost(step, SCOPES)
