"""The whole train path of models/kimi_linear.py (Kimi-Linear-48B-A3B, PR 64)
at the tiny preset (a dense KDA layer, two periods of a KDA and an MLA layer
and a tail of one MLA layer over 12 sigmoid-routed experts), seeded weights,
against the plain reference (chipbench/reference/kimi_linear_decoder.py,
which runs the recurrence position by position): logits, loss, every expert
layer's counts and every gradient leaf; each reading of the equations NOT
taken told from the one taken on the same path; and the contract's two
compiled cases for the same row (tests/model_cases.py), which read the same
memo. The stack's plan, the blocks and the shares: tests/test_kimi_linear.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import kimi_linear_decoder as ref
from chipbench.tools.kimi_linear_wrong import PRECISION_ONLY, VARIANTS
from model_cases import KIMI_LINEAR, contract_cases, reference_path, train_path, worst_leaf
from ray_tpu.models import llama

FP32 = KIMI_LINEAR.fp32
HIGHEST = jax.default_matmul_precision("highest")


def test_train_path_meets_the_reference_in_logits_loss_routing_and_gradients():
    """The one train path (llama.loss_and_weight_fn through the stack: the
    dense layer, the scan over two periods, the tail) in float32 against the
    reference: the loss, every expert layer's counts, every gradient leaf (the
    selection bias takes none on either side), the logits."""
    ours, theirs = train_path(KIMI_LINEAR, FP32), reference_path(KIMI_LINEAR, FP32)
    assert float(ours.loss) == pytest.approx(float(theirs.parts["loss"]), rel=2e-6)
    np.testing.assert_array_equal(np.asarray(ours.stats["tokens_per_expert"]),
                                  np.asarray(theirs.parts["tokens_per_expert"]))
    assert ours.stats["tokens_per_expert"].shape == (5, 12)
    assert int(ours.stats["dropped_pairs"].sum()) == 0
    worst = worst_leaf(ours.grads, theirs.grads)
    assert len(worst) == len(jax.tree.leaves(ours.params)) - 1 and max(worst.values()) < 2e-4, worst
    assert {"dense_layers", "layers"} <= set(ours.params) and "tail" in ours.params["layers"]
    shape = KIMI_LINEAR.shape_of(FP32)
    with HIGHEST:
        logits = jax.jit(lambda p, t: llama.forward(p, t, FP32))(ours.params, ours.batch["tokens"])
    # one program for both sequences: taken bare, the reference's every operation is compiled alone
    theirs_logits = jax.jit(lambda p, t: ref.logits(p, t, shape))
    want = jnp.stack([theirs_logits(ours.params, ours.batch["tokens"][b]) for b in range(2)])
    assert float(jnp.abs(logits - want).max()) < 2e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("name", [n for n in VARIANTS if n not in PRECISION_ONLY],
                         ids=lambda n: n.replace(" ", "_"))
def test_each_reading_not_taken_is_told_from_the_one_taken(name):
    """The program's loss against the reference changed in ONE thing (the
    changes of the cell's one-thing-wrong table,
    chipbench/tools/kimi_linear_wrong.py: a rotary on the 64 channels, scale
    128^-1/2 and beta doubled first among them): far outside what the sound
    comparison leaves (2e-6)."""
    ours = train_path(KIMI_LINEAR, FP32)
    shape = KIMI_LINEAR.shape_of(FP32)
    with VARIANTS[name]():
        wrong = jax.jit(lambda p, t, y: ref.sequence(p, t, y, shape)[0])
        total = sum(wrong(ours.params, ours.batch["tokens"][b], ours.batch["targets"][b])
                    for b in range(2)) / ours.batch["tokens"].size
    assert not abs(float(total) - float(ours.loss)) <= 1e-4 * float(ours.loss), name


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    KIMI_LINEAR)
