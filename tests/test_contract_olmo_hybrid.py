"""Olmo-Hybrid's whole train path (PR 46) at a small size on the CPU (one
and two periods of three linear layers and a full one, 150 positions),
seeded weights, against the plain reference
(chipbench/reference/olmo_hybrid_decoder.py, which runs the recurrence
position by position): logits, loss and every gradient; the readings the
configuration file's `assumed` did NOT take, each told from the one it
took on the same path; and the contract's two compiled cases for the same
row (tests/model_cases.py), which read the same memo: this process
compiles the one-period path once for all. The sublayers, the
convolution and the shares: tests/test_olmo_hybrid.py."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench.reference import olmo_hybrid_decoder as ref
from chipbench.tools.olmo_hybrid_wrong import PRECISION_ONLY, VARIANTS
from model_cases import OLMO_HYBRID, contract_cases, reference_path, train_path, worst_leaf
from ray_tpu.models import llama

FP32, B = OLMO_HYBRID.fp32, OLMO_HYBRID.batch


@pytest.mark.parametrize("n_layers,grad_tol,logit_tol", [(4, 2e-3, 2e-5), (8, 3e-2, 5e-4)],
                         ids=["one_period", "two_periods"])
def test_train_path_meets_the_reference_in_logits_loss_and_gradients(n_layers, grad_tol, logit_tol):
    """The loss to 1e-5 at both depths. Logits and gradients to what
    float32 leaves after a stack whose norms sit on the sublayers'
    OUTPUTS: such a norm divides the Jacobian by the size of what it
    norms, a fresh full-attention layer's output is small, and rounding
    grows about a hundredfold a period (tests/model_cases.py has the
    readings: the program against ITSELF rematerialised differs by 1e-4
    after four layers and 1e-2 after eight). So ONE period holds every
    gradient to 2e-3 of its leaf's largest (seen: 5e-4) and the logits to
    2e-5 of theirs; two periods, which the scan over periods needs, hold
    them to 3e-2 (seen: 8e-3) and 5e-4 (seen: 6e-5)."""
    cfg = dataclasses.replace(FP32, n_layers=n_layers)
    shape = OLMO_HYBRID.shape_of(cfg)
    ours, theirs = train_path(OLMO_HYBRID, cfg), reference_path(OLMO_HYBRID, cfg)
    assert ours.stats is None
    assert float(ours.loss) == pytest.approx(float(theirs.parts["loss"]), rel=1e-5)
    worst = worst_leaf(ours.grads, theirs.grads)
    assert len(worst) == len(jax.tree.leaves(ours.params)) and max(worst.values()) < grad_tol, worst
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: llama.forward(p, t, cfg))(ours.params, ours.batch["tokens"])
    want = jnp.stack([ref.logits(ours.params, ours.batch["tokens"][b], shape) for b in range(B)])
    assert float(jnp.abs(logits - want).max()) < logit_tol * float(jnp.abs(want).max())


@pytest.mark.parametrize("name", [n for n in VARIANTS if n not in PRECISION_ONLY],
                         ids=lambda n: n.replace(" ", "_"))
def test_each_reading_not_taken_is_told_from_the_one_taken(name):
    """The program's loss against the reference changed in ONE thing (the
    changes of the cell's one-thing-wrong table,
    chipbench/tools/olmo_hybrid_wrong.py: the reordered norm, the missing
    rotary, the doubled beta, the decay, the convolution, the L2 norms,
    the output gate): far outside what the sound comparison leaves (1e-5)."""
    cfg = dataclasses.replace(FP32, n_layers=4)   # one period: the gradient test's own path
    ours = train_path(OLMO_HYBRID, cfg)
    with VARIANTS[name]():
        wrong = ref.loss(ours.params, ours.batch["tokens"], ours.batch["targets"],
                         OLMO_HYBRID.shape_of(cfg))
    # (without the L2 norms the rule's eigenvalue leaves (-1, 1) and the loss is not a number)
    assert not abs(float(wrong) - float(ours.loss)) <= 1e-3 * float(ours.loss), name


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    OLMO_HYBRID)
