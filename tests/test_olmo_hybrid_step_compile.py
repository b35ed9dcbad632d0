"""The step of `olmo-hybrid-train` for a described v5e (tests/v5e_steps.py),
compiled ONCE: Olmo-Hybrid-7B's one period (three gated-delta-rule linear
layers and a full one over a SwiGLU of 11008, an eighth of the vocabulary
held, 1 x 4096) as the cell builds it. A file of the cell's own (PR 45's
layout: a full-width compile is 40 s alone and takes every core; ROADMAP
D8)."""

import re

from v5e_steps import train_step, v5e  # noqa: F401 - a fixture

OLMO_HYBRID = dict(batch=1, model="olmo-hybrid-7b", n_layers=4, vocab_size=12544)
# sha256 of the lowered step of olmo-hybrid-7b as `olmo-hybrid-train` builds it, as PR 46
# lowers it (the account of every hash is tests/test_m7b_steps_compile.py's)
_OLMO_HYBRID_STEP = "f192679aac821603ebacfda444694e75d8313de20a7fd300b3ff0d88739022b5"
# the other configuration whose stack goes through models/llama.py's seam (`stack_module`, PR 46):
# laguna-s-2.1 as `laguna-train` builds it, lowered by PR 46 AND by its parent (5c794fa) to
# the same text; no test held this hash before
LAGUNA = dict(batch=1, model="laguna-s-2.1", n_layers=5, vocab_size=12544, experts_held=8)
_LAGUNA_STEP = "0b2bb23b3f4879e8be615653809d840670112e13163f44f4d7c7ca8e81733150"
GIB = 2 ** 30


def test_olmo_hybrid_train_step_lowers_to_the_text_it_had(v5e):
    assert train_step(v5e, **OLMO_HYBRID).lowered_hash() == _OLMO_HYBRID_STEP


def test_laguna_train_step_lowers_through_the_seam_to_the_parents_text(v5e):
    """Lowered only (ten seconds): the seam is Python dispatch at trace time."""
    assert train_step(v5e, **LAGUNA).lowered_hash() == _LAGUNA_STEP


def test_olmo_hybrid_train_step_fits_the_chip_and_runs_the_rule_in_loops_over_chunks(v5e):
    """What the rehearsal of ISSUE 46's step 4 (a) found, held: with the
    remat policy "dots" as it is the step is 10.38 GiB of arguments (928.9M
    parameters x 12 B) + 4.70 of temporaries, inside the chip's 15.75;
    the only Pallas kernels are the full layer's flash forward and its
    fused backward at 30 / 30 heads of 128, named after their scope; each
    linear layer's recurrence is three loops of 4096 / 64 = 64 trips
    under `gdn.scan` (forward, the rematerialised forward, the
    transpose) that carry a [1, 30, 96, 192] float32 state, and no loop
    of 4,096 trips is anywhere; no array is [4096, 4096]; every scope the
    cell's readers sum is in the compiled step, and the sublayer counts
    its sites."""
    step = train_step(v5e, **OLMO_HYBRID)
    engaged = step.engaged("gdn.attn", "flash.bwd_fused", "flash.bwd_split", "tp_overlap.plain",
                           "grouped_matmul.ragged_dot")
    assert engaged["gdn.attn"] >= 3 and engaged["flash.bwd_fused"] == 1
    assert engaged["flash.bwd_split"] == engaged["tp_overlap.plain"] == 0   # fallback_sites
    assert engaged["grouped_matmul.ragged_dot"] == 0
    assert step.memory.argument_size_in_bytes < 10.39 * GIB
    assert step.memory.temp_size_in_bytes < 4.80 * GIB
    assert (step.memory.argument_size_in_bytes + step.memory.temp_size_in_bytes) < 15.75 * GIB
    hlo, kernels = step.hlo, step.kernels
    assert sorted(re.sub(r"\.\d+$", "", k) for k in kernels) == ["attn.attend"] * 2, kernels
    assert re.search(r"bf16\[1,30,4096,128\]", hlo)
    loops = re.findall(r'= (\([^\n]*?\)) while\([^\n]*op_name="([^"]*)"', hlo)
    scans = [(carried, name) for carried, name in loops if "gdn.scan" in name]
    assert len(scans) == 9 and len(loops) == 9, [name for _, name in loops]
    for carried, name in scans:   # 64 chunks stacked, one state carried
        assert "f32[64,1,30,64," in carried and "f32[1,30,96,192]" in carried, name
    assert sum("rematted_computation" in name for _, name in scans) == 3
    assert sum("transpose(" in name and "rematted_computation" not in name for _, name in scans) == 3
    # nothing is stacked over the 4,096 positions: no loop walks them one at a time
    assert not re.search(r"\[4096,1,30,", hlo)
    assert "f32[1,30,96,192]" in hlo and not re.search(r"\[(?:\d+,)*4096,4096\]", hlo)
    for scope in ("gdn.proj", "gdn.conv", "gdn.gates", "gdn.scan", "gdn.norm", "gdn.out",
                  "attn.qkv", "attn.rope", "attn.attend", "attn.out", "dense.ffn", "block.norm",
                  "block.stack", "embed", "head", "optim"):
        assert step.has_scope(scope), scope
    # head-major from the projections to `wo` in the full layer: no copy or transpose of q, k, v
    moved = [shape for shape, op in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose)\(", hlo, re.M)
        if re.search(r"bf16\[1,30,4096,128\]|bf16\[1,4096,30,128\]", shape)]
    assert not moved, moved
