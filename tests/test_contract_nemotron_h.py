"""The whole train path of models/nemotron_h.py (the causal tower of
Nemotron-Labs-TwoTower-30B-A3B, PR 49) at the tiny preset with all three
kinds of layer, seeded weights, against the plain reference
(chipbench/reference/nemotron_h_decoder.py, which runs the scan position
by position): logits, loss, every expert layer's counts and every
gradient; each reading of the equations NOT taken told from the one taken
on the same path; and the contract's two compiled cases for the same row
(tests/model_cases.py), which read the same memo. The stack's plan, the
sublayers and the shares: tests/test_nemotron_h.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h_decoder as ref
from chipbench.tools.nemotron_h_wrong import PRECISION_ONLY, VARIANTS
from model_cases import NEMOTRON_H, contract_cases, reference_path, train_path, worst_leaf
from ray_tpu.models import llama

FP32 = NEMOTRON_H.fp32
HIGHEST = jax.default_matmul_precision("highest")


def test_train_path_meets_the_reference_in_logits_loss_routing_and_gradients():
    """The one train path (llama.loss_and_weight_fn through the stack's
    scan over `ME*` x 2 and its unrolled tail) in float32 against the
    reference: the loss, every expert layer's counts, every gradient leaf
    (the selection bias takes none on either side), the logits."""
    ours, theirs = train_path(NEMOTRON_H, FP32), reference_path(NEMOTRON_H, FP32)
    assert float(ours.loss) == pytest.approx(float(theirs.parts["loss"]), rel=2e-6)
    np.testing.assert_array_equal(np.asarray(ours.stats["tokens_per_expert"]),
                                  np.asarray(theirs.parts["tokens_per_expert"]))
    assert int(ours.stats["dropped_pairs"].sum()) == 0
    worst = worst_leaf(ours.grads, theirs.grads)
    assert len(worst) == len(jax.tree.leaves(ours.params)) - 1 and max(worst.values()) < 2e-4, worst
    shape = NEMOTRON_H.shape_of(FP32)
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
    chipbench/tools/nemotron_h_wrong.py): far outside what the sound
    comparison leaves (2e-6)."""
    ours = train_path(NEMOTRON_H, FP32)
    with VARIANTS[name]():
        wrong = ref.loss(ours.params, ours.batch["tokens"], ours.batch["targets"],
                         NEMOTRON_H.shape_of(FP32))
    assert not abs(float(wrong) - float(ours.loss)) <= 1e-4 * float(ours.loss), name


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    NEMOTRON_H)
