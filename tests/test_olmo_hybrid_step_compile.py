"""The step of `olmo-hybrid-train` for a described v5e (tests/v5e_steps.py),
compiled ONCE: Olmo-Hybrid-7B's one period (three gated-delta-rule linear
layers and a full one over a SwiGLU of 11008, an eighth of the vocabulary
held, 1 x 4096) as the cell builds it, which is also the guard that
ops/gated_delta.py's and ops/gdn_conv.py's kernels lower through Mosaic
at heads of 96 / 192 where no chip is at hand. A file of the cell's own (PR 45's
layout: a full-width compile is 40 s alone and takes every core; ROADMAP
D8)."""

import re

from v5e_steps import train_step, v5e  # noqa: F401 - a fixture

OLMO_HYBRID = dict(batch=1, model="olmo-hybrid-7b", n_layers=4, vocab_size=12544)
# sha256 of the lowered step of olmo-hybrid-7b as `olmo-hybrid-train` builds it, as PR 48
# lowers it: the rule as two `pallas_call`s a layer (PR 47) and the convolution, SiLU and L2
# norms before it as two a tensor (the account of every hash is
# tests/test_m7b_steps_compile.py's; the kernels' own bodies are not in it)
_OLMO_HYBRID_STEP = "68b139dadb3f7426e556122e7adf2d0c859bcfe78b76edb72e3d609a81dcf6f8"
# the other configuration whose stack goes through models/llama.py's seam (`stack_module`, PR 46):
# laguna-s-2.1 as `laguna-train` builds it, lowered by PR 46 AND by its parent (5c794fa) to
# the same text, and by PR 47, which adds two names to `llama._remat`'s list that no other
# program carries, and by PR 48, which touches nothing another model imports
LAGUNA = dict(batch=1, model="laguna-s-2.1", n_layers=5, vocab_size=12544, experts_held=8)
_LAGUNA_STEP = "0b2bb23b3f4879e8be615653809d840670112e13163f44f4d7c7ca8e81733150"
GIB = 2 ** 30


def test_olmo_hybrid_train_step_lowers_to_the_text_it_had(v5e):
    assert train_step(v5e, **OLMO_HYBRID).lowered_hash() == _OLMO_HYBRID_STEP


def test_laguna_train_step_lowers_through_the_seam_to_the_parents_text(v5e):
    """Lowered only (ten seconds): the seam is Python dispatch at trace time."""
    assert train_step(v5e, **LAGUNA).lowered_hash() == _LAGUNA_STEP


def test_olmo_hybrid_train_step_fits_the_chip_and_runs_the_rule_in_kernels(v5e):
    """The step with the rule (PR 47) and the convolution, SiLU and L2
    norms before it (PR 48) as Pallas kernels, lowered through Mosaic at
    heads of 96 / 192 for the described chip: with the remat policy
    "dots" as it is the step is 10.38 GiB of arguments (928.9M parameters
    x 12 B) + 3.89 of temporaries (4.28 with the jax.numpy convolution,
    4.70 with the jax.numpy scan too), inside the chip's 15.75; the Pallas
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
    chain is saved but the bfloat16 projection) and backward, with no
    float32 pass of XLA's own over a [1, 30, 4096, d] array left under
    that scope. No
    `while` is left in the step (the walk over the chunks is the kernels'
    grid), no chunked array [64, 1, 30, 64, ...] and no triangular solve;
    no array is [4096, 4096]; every scope the cell's readers sum is in
    the compiled step, and the sublayer and the rule count their sites."""
    step = train_step(v5e, **OLMO_HYBRID)
    engaged = step.engaged("gdn.attn", "gated_delta.kernel", "gdn_conv.kernel", "flash.bwd_fused",
                           "flash.bwd_split", "tp_overlap.plain", "grouped_matmul.ragged_dot")
    assert engaged["gdn.attn"] >= 3 and engaged["gated_delta.kernel"] >= 3
    assert engaged["gdn_conv.kernel"] >= 9          # q, k and v of each linear layer
    assert engaged["flash.bwd_fused"] == 1
    assert engaged["flash.bwd_split"] == engaged["tp_overlap.plain"] == 0   # fallback_sites
    assert engaged["grouped_matmul.ragged_dot"] == 0
    assert step.memory.argument_size_in_bytes < 10.39 * GIB
    assert step.memory.temp_size_in_bytes < 4.00 * GIB
    assert (step.memory.argument_size_in_bytes + step.memory.temp_size_in_bytes) < 15.75 * GIB
    hlo, kernels = step.hlo, step.kernels
    # named after the scope they stand in, or after the jitted function that holds them
    assert sorted(re.sub(r"\.\d+$", "", k) for k in kernels) == (
        ["attn.attend"] * 2 + ["gated_delta_bwd"] * 3 + ["gated_delta_fwd"] * 3
        + ["gdn_conv_bwd"] * 9 + ["gdn_conv_fwd"] * 18), kernels
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
    for scope in ("gdn.proj", "gdn.conv", "gdn.gates", "gdn.scan", "gdn.norm", "gdn.out",
                  "attn.qkv", "attn.rope", "attn.attend", "attn.out", "dense.ffn", "block.norm",
                  "block.stack", "embed", "head", "optim"):
        assert step.has_scope(scope), scope
    # head-major from the projections to `wo` in the full layer: no copy or transpose of q, k, v
    moved = [shape for shape, op in re.findall(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) (copy|transpose)\(", hlo, re.M)
        if re.search(r"bf16\[1,30,4096,128\]|bf16\[1,4096,30,128\]", shape)]
    assert not moved, moved
