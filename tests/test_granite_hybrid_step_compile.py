"""The step of `granite-h-micro-train-packed` for a described v5e
(tests/v5e_steps.py), in a file of its cell's own (PR 45's rule):
granite-4.0-h-micro's first period (5 Mamba-2 layers, 1 NoPE GQA layer, 4
Mamba-2 layers; ALL 64 Mamba heads in ONE group, an eighth of the tied
table, 1 x 8192 under remat "full") as the cell builds it, on a batch with
`segment_ids` and `mask`. THE LANE READS THE LOWERED MODULE (PR 68: one
lowering for the file, no compile): the arguments' 8.63 GiB, the kernels by
site, the traced sites, every scope the cell's readers sum. What only the
compile shows is ONE case marked `slow`
(`python -m pytest -m slow tests/test_granite_hybrid_step_compile.py`, 54 s
alone on this sandbox, PR 68): that the step FITS (the compiler's peak under
the chip's 15.75 GiB), which is also the guard that ops/ssd.py's kernels lower
through Mosaic at a group of 64 heads (with the VMEM they ask for, 31 and 41
MiB: tests/test_ssd_documents.py; at the default 16 the compile is refused)
with the documents' rows, ops/gdn_conv.py's with the distances, and
ops/flash.py's under `segment_ids` at heads of 64. Every PR's run of the
cell on the chip shows the same (`hbm_peak_gib.train`, the step's table by
scope)."""

import re

import pytest

from v5e_steps import Step, scopes_lost, v5e  # noqa: F401 - a fixture

GRANITE = dict(batch=1, model="granite-4.0-h-micro", n_layers=10, seq=8192, vocab_size=12544,
               remat_policy="full")
GIB = 2 ** 30
SCOPES = ("ssm.proj", "ssm.conv", "ssm.gates", "ssm.scan", "ssm.norm", "ssm.out", "attn.qkv",
          "attn.attend", "attn.out", "dense.ffn", "block.norm", "block.stack", "embed", "head",
          "optim")
_STEP = []


def packed_step(devices) -> Step:
    """The file's one record: the cell's step on a PACKED batch (the shared
    builder's batch has tokens and targets alone)."""
    if not _STEP:
        step = Step(devices, **GRANITE)
        tokens = step.batch["tokens"]
        step.batch = {**step.batch, "segment_ids": tokens, "mask": tokens}
        _STEP.append(step)
    return _STEP[0]


KERNEL_SITES = (("ssd_scan_fwd", 4), ("ssd_scan_bwd", 2), ("gdn_conv_fwd", 4), ("gdn_conv_bwd", 2),
                ("gated_norm_fwd", 4), ("gated_norm_bwd", 2))


def test_granite_hybrid_train_step_fits_the_chip(v5e):
    """772,160,448 parameters x 12 B = 8.63 GiB of arguments, summed from
    the step's abstract inputs: over a quarter of the chip by the arguments
    alone, the benchmark's floor. That the compiler's PEAK fits is the slow
    case's, and `hbm_peak_gib.train`'s on the chip."""
    arguments = packed_step(v5e).argument_bytes
    assert 8.62 * GIB < arguments < 8.64 * GIB
    assert arguments > 0.25 * 16 * GIB


def test_granite_hybrid_train_step_runs_the_kernels_where_the_readers_look(v5e):
    """Nine Mamba layers in two scans and one attention layer, as the step is
    LOWERED: a kernel is ONE site a scan's body, forward, the forward made
    again under remat "full", and backward; the Mamba block is traced ONCE
    for both scans (one counted site), and the attention layer's backward is
    the fused kernel (`fallback_sites.train` 0). Since PR 69 each of the
    layer's THREE kernel calls is built with the documents' range: 8,192
    keys at heads of 64 are one kv block, so under `segment_ids` a q block's
    walk starts at its own documents' first sub-tile (`flash.doc_walk`:
    counted where a call is built, which is the forward traced twice, once
    as the layer's value and once for its derivative, and the fused
    backward once: the three sites of `attn.attend`)."""
    step = packed_step(v5e)
    kernels = step.lowered_kernels
    for name, sites in KERNEL_SITES:
        assert kernels.count(name) == sites, (name, kernels.count(name))
    assert kernels.count("attn.attend") == 3   # forward twice, backward fused
    assert step.engaged("ssd_scan.kernel", "gdn_conv.kernel", "flash.bwd_fused",
                        "flash.bwd_split", "flash.doc_walk") == {
        "ssd_scan.kernel": 1, "gdn_conv.kernel": 1, "flash.bwd_fused": 1, "flash.bwd_split": 0,
        "flash.doc_walk": 3}


@pytest.mark.parametrize("scope", SCOPES)
def test_granite_hybrid_train_step_has_every_scope_its_readers_sum(v5e, scope):
    assert packed_step(v5e).has_scope(scope, lowered=True)


@pytest.mark.slow
def test_granite_hybrid_train_step_compiles_for_the_chip_and_fits_it(v5e):
    """The step COMPILED, outside the tier-1 clock: the arguments are what
    the abstract inputs sum to; the compiler's own peak 14.28 GiB of the
    chip's 15.75 (the rehearsal of ISSUE 66's step 3 (b): remat "full";
    under "dots" the same step is refused at 19.58). `temp_size_in_bytes`
    reads 8.39 GiB, of which the compiler's own report calls more than half
    fragmentation: the peak is what has to fit. The kernels are Mosaic's at
    the lowered module's sites under their names; no kernel is XLA's own
    rematerialisation's; every scope outlives the compile."""
    step = packed_step(v5e)
    memory = step.memory
    assert 8.62 * GIB < memory.argument_size_in_bytes < 8.64 * GIB
    assert memory.peak_memory_in_bytes < 14.6 * GIB < 15.75 * GIB
    kernels = [re.sub(r"\.\d+$", "", k) for k in step.kernels]
    for name, sites in KERNEL_SITES:
        assert kernels.count(name) == sites, (name, kernels.count(name))
    assert sum(k.startswith("attn.attend") for k in kernels) == 3   # forward twice, backward fused
    assert not re.search(r"\.remat\d* = ", step.hlo)
    assert not scopes_lost(step, SCOPES)
