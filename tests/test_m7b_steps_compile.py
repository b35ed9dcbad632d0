"""The dense steps (Mistral-7B wide, 2 layers) for a described v5e
(tests/v5e_steps.py): `m7b-train`'s on one chip at batch 3 and
`m7b-train-4chip`'s under fsdp 2 x tp 2 at batch 6. THE LANE READS THE
LOWERED MODULES (PR 68: one lowering a step, no compile): the text each
lowers to, no trace of the overlap path without a mesh and the blocks'
collective-permutes with one, the VMEM the step asks the compiler for, every
scope the cells' readers sum. What only a compile shows is ONE case a step
marked `slow` (`python -m pytest -m slow tests/test_m7b_steps_compile.py`:
35 and 40 s alone on this sandbox, PR 68): the `tp` transfers started before a
matmul and done after it, the tiles the matmul fusions are cut into at that
VMEM, the temporaries' bytes, the scopes that outlive XLA's fusion. Every
PR's run of the two cells on the chip shows the same (`train_tok_s`,
`hbm_peak_gib.train`, the step's table by scope, `coll_exposed_pct`)."""

import re
import sys

import pytest

from v5e_steps import (Step, matmul_tiles, scopes_lost, train_step,  # noqa: F401
                       v5e)

MESH = (1, 1, 2, 1, 1, 2)
# sha256 of the lowered train step of mistral-7b (2 layers, flash, AdamW), as PR 38
# (the full-attention sublayer head-major from its projections to `wo`) lowers it, the
# flash kernels' serialized bodies taken out (they embed source locations); from commit
# 5b629f1 (the parent of PR 26) to PR 37 it was 14345d8a... / dd35b02d.... A change
# that MEANS to alter the dense step prints the new text's hash in the failure and
# replaces these.
# the scopes chipbench/step_scopes/base.json sums for a dense cell
DENSE_SCOPES = ("embed", "block.stack", "block.norm", "attn.qkv", "attn.rope", "attn.attend",
                "attn.out", "dense.ffn", "head", "optim")
_DENSE_STEP = {
    None: "e735d680c01a71bc9f75193edc03cd16e2d207738ff990ed5a6cb0e7dddeca3f",
    MESH: "bdea6ab54b92ac603d3d65a9b55c170f53065ddf003ac3aa93407b36fb810b02",
}


@pytest.mark.parametrize("mesh_shape,batch", [(None, 3), (MESH, 6)],
                         ids=["one_chip", "fsdp2_tp2"])
def test_dense_train_step_lowers_to_the_text_it_had_before_the_expert_layer(
        v5e, mesh_shape, batch):
    """One block serves dense and expert configurations (PR 26); for a
    dense one the lowered step is the text it was, which is what keeps
    `m7b-train` and `m7b-train-4chip` where they are. And one flash
    path serves both of its entries (PR 33: `flash_attention` is its
    transposes around the head-major one that CCA calls): the steps
    that enter by the old one, OLMoE's too, lower to the text they had.
    And PR 34's third kind of attention, sigmoid scores, shared expert,
    dense layers before the scan and second head leave all four, ZAYA1's
    with them, the text they had. PR 38 MEANT to alter the three steps
    that run the full-attention branch (head-major from the projections
    to `wo`) and replaced their hashes; ZAYA1's and GLM-4.7-Flash's,
    which bypass that branch, keep the text their parents gave them.
    PR 40 MEANT to alter the steps of the SMALL shares (GLM-4.7-Flash's
    hash replaced; Laguna's step is held by its own tests): the dense
    steps, OLMoE's (every expert held) and ZAYA1's (a half share: no
    compact path is built) keep theirs. PR 44 MEANT to alter the small
    shares whose [N, C] is large (GLM-4.7-Flash's hash replaced again;
    Keye's step is held by its own test): the four others never reach
    the sum of the held rows and keep theirs. The expert steps' cases
    stand with their steps: ZAYA1's in
    tests/test_zaya1_keye_steps_compile.py, OLMoE's and GLM-4.7-Flash's in
    tests/test_glm47f_laguna_steps_compile.py."""
    assert train_step(v5e, mesh_shape, batch=batch).lowered_hash() == _DENSE_STEP[mesh_shape]


def test_one_chip_train_step_never_asks_for_tp_overlap(v5e, monkeypatch):
    """No mesh: `_block` takes the plain einsums and does not even import
    parallel/tp_overlap.py — the lowered step is the same text with the
    module loaded and with its import made to fail (a FRESH trace, round
    the memo)."""
    import ray_tpu.parallel.tp_overlap  # noqa: F401 - loaded

    with_module = train_step(v5e, batch=3).lowered_text
    monkeypatch.setitem(sys.modules, "ray_tpu.parallel.tp_overlap", None)
    with pytest.raises(ImportError):
        import ray_tpu.parallel.tp_overlap  # noqa: F401,F811
    assert Step(v5e, batch=3).lowered_text == with_module
    assert "tpu_custom_call" in with_module and "collective_permute" not in with_module


def test_tp_matmuls_of_the_train_step_overlap_their_transfers(v5e):
    """The fsdp 2 x tp 2 train step of `m7b-train-4chip` (2 layers) as it is
    LOWERED: the `tp` matmuls are parallel/tp_overlap.py's (no site took the
    plain einsums), so the residual stream's blocks travel by
    collective-permute beside the flash kernels. That the compiler STARTS a
    block's transfer before a matmul and finishes it after, and that no
    layer scan waits for an all-reduce of the residual stream, is the
    schedule's to show: the slow case below, and `coll_exposed_pct` on the chip."""
    step = train_step(v5e, MESH, batch=6)
    # the block is traced once for the layer scan: a gather before and a scatter after each of
    # its two sublayers, and no site on the plain einsums
    assert step.engaged("tp_overlap.ag_matmul", "tp_overlap.rs_matmul", "tp_overlap.plain") == {
        "tp_overlap.ag_matmul": 2, "tp_overlap.rs_matmul": 2, "tp_overlap.plain": 0}
    text = step.lowered_text
    assert "tpu_custom_call" in text
    assert text.count("collective_permute") >= 4


@pytest.mark.parametrize("mesh_shape,batch", [(None, 3), (MESH, 6)],
                         ids=["m7b_train", "m7b_train_4chip"])
def test_train_steps_compile_with_the_vmem_their_operations_are_given(v5e, mesh_shape, batch):
    """train/step.py gives one operation of the step 32 MiB of a v5e core's
    VMEM where XLA's default is 16: every dense cell's step, built for the
    described chip, carries that limit to its compile and nothing else.
    What the limit buys (the matmul fusions' tiles, the temporaries) is the
    compiler's to show: the slow case below."""
    step = train_step(v5e, mesh_shape, batch=batch)
    assert step.compiler_options == {"xla_tpu_scoped_vmem_limit_kib": 32 * 1024}


@pytest.mark.parametrize("scope", DENSE_SCOPES)
@pytest.mark.parametrize("mesh_shape,batch", [(None, 3), (MESH, 6)], ids=["one_chip", "fsdp2_tp2"])
def test_dense_train_steps_hold_the_scope_their_readers_sum(v5e, mesh_shape, batch, scope):
    """A scope the step's table sums for `m7b-train` and `m7b-train-4chip`
    (chipbench/step_scopes/base.json) is on an operation of the LOWERED
    step (the file's one lowering of each step, tests/v5e_steps.py's memo).
    A case a scope and step: a failure names the scope. That the scope
    OUTLIVES the compile, where a trace's readers find it (a scope whose
    operations XLA fuses into another's or eliminates shows nothing in a
    trace), is the slow case's and the chip's own table's."""
    assert train_step(v5e, mesh_shape, batch=batch).has_scope(scope, lowered=True), scope


@pytest.mark.slow
@pytest.mark.parametrize("mesh_shape,batch,temp_gib,tiles_at_16", [
    (None, 3, 11.2, 37144),
    (MESH, 6, 5.4, 10532),
], ids=["m7b_train", "m7b_train_4chip"])
def test_dense_train_steps_compile_for_the_chip(v5e, mesh_shape, batch, temp_gib, tiles_at_16):
    """Each dense cell's step COMPILED, outside the tier-1 clock. With 32
    MiB of VMEM an operation where XLA's default is 16, which is what the
    matmul fusions are tiled for (the head's weight gradient with the
    optimizer's update in it first of all: 84 x 8 x 13 tiles in `m7b-train`,
    84 x 4 x 10 now), its matmul fusions are cut into fewer than half the
    tiles they have at 16 MiB; the temporaries stay where they were (10.98,
    6.62 and 5.11 GiB at 16 MiB: past 11.2 `m7b-train` rematerialises); and
    what the limit is bought with is still there: XLA keeps whole arrays in
    the VMEM no operation claims, and the expert layer's token gathers read
    their 96 MiB table [24576, 2048] from it, five times as fast as from
    HBM. From 40 MiB the table no longer fits and `olmoe-train` loses what
    its matmuls gain (PERF.md, PR 29). The dense cells' two cases;
    `olmoe-train`'s stands with its step in
    tests/test_glm47f_laguna_steps_compile.py. Every scope the cell's
    readers sum outlives the compile. Under the mesh: neither layer scan,
    forward or backward, waits for an all-reduce of the residual stream; the
    blocks travel by collective-permute, which the compiler starts before a
    matmul and finishes after it."""
    step = train_step(v5e, mesh_shape, batch=batch)
    hlo, computations = step.hlo, step.computations
    assert 0 < matmul_tiles(hlo) < 0.5 * tiles_at_16
    assert step.memory.temp_size_in_bytes < temp_gib * 2 ** 30
    assert not scopes_lost(step, DENSE_SCOPES)
    assert "tpu_custom_call" in hlo
    if mesh_shape is None:
        return
    bodies = [computations[name] for name in set(re.findall(r"body=%?([\w.\-]+)", hlo))
              if "tpu_custom_call" in computations[name]]  # the two layer scans
    assert len(bodies) == 2
    for body in bodies:
        assert not re.search(r"= bf16\[\d+,4096,4096\]\S* all-reduce(-start)?\(", body)
        # scheduled text: a matmul fusion between each block's start and its done
        matmuls = [m.start() for m in re.finditer(r" fusion\([^\n]*calls=%?([\w.\-]+)", body)
                   if " convolution(" in computations[m.group(1)]]
        blocks = list(re.finditer(
            r"%([\w.\-]+) = \(bf16\[3,2048,4096\][^=]*? collective-permute-start\(", body))
        assert len(blocks) >= 4, "two gathers and two scatters a layer and direction"
        for start in blocks:
            done = body.index(f" collective-permute-done(%{start.group(1)})")
            assert any(start.start() < at < done for at in matmuls), start.group(1)
