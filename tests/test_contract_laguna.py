"""The typed stack's whole train path (models/laguna.py) at a small size
on the CPU, seeded weights, each of its two models against its own plain
reference: Laguna (PR 39) over a dense layer and two periods, Mellum2
(PR 53) over two periods, in loss, tokens per expert and every gradient;
Mellum2's one-thing-wrong table at the tiny size; and the contract's two
compiled cases for both rows (tests/model_cases.py), which read the same
memos. The sublayers, the routing and the shares: tests/test_laguna.py."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench.reference import mellum2_decoder
from chipbench.tools import mellum2_wrong
from model_cases import (LAGUNA, MELLUM2, contract_cases, reference_path, seeded_params,
                         train_path, worst_leaf)

M_FP32 = MELLUM2.fp32


@pytest.mark.parametrize("model,held,bias", [
    (LAGUNA, None, 0.0), (LAGUNA, None, 0.05), (LAGUNA, (4, 8), 0.0), (LAGUNA, (4, 8), 0.05),
    # Mellum2's one: a share under a random table (its published forward whole is held in loss
    # by the contract's bf16 case and in every mechanism by the one-thing-wrong table below;
    # each more case compiles the stack's scan and the reference's eight layers again)
    (MELLUM2, (4, 8), 0.05)],
    ids=lambda v: getattr(v, "name", None) or {None: "all_experts", (4, 8): "a_share",
                                               0.0: "zero_bias", 0.05: "random_bias"}[v])
def test_train_path_meets_the_reference_in_loss_and_gradients(model, held, bias):
    """llama.loss_fn (the one train path) on two periods of four (under
    Laguna's dense layer) against the plain reference: the loss, the
    tokens per expert of every block, and every gradient by its worst
    leaf."""
    FP32, B, S = model.fp32, model.batch, model.seq
    cfg = FP32 if held is None else dataclasses.replace(
        FP32, experts_held=held[0], first_expert_held=held[1])
    ours, theirs = train_path(model, cfg, bias), reference_path(model, cfg, bias)
    loss, weight, stats, ref = ours.loss, ours.weight, ours.stats, theirs.parts
    assert float(weight) == B * S
    assert float(loss) == pytest.approx(float(ref["loss"]), rel=2e-6)
    assert stats["tokens_per_expert"].shape == (8, cfg.n_experts)
    assert stats["tokens_per_expert"].tolist() == ref["tokens_per_expert"].tolist()
    assert stats["tokens_per_expert"].sum(-1).tolist() == [cfg.top_k * B * S] * 8
    assert int(stats["dropped_pairs"].sum()) == 0
    if held is not None:
        n, first = held
        elsewhere = cfg.top_k * B * S - stats["tokens_per_expert"][:, first:first + n].sum(-1)
        assert stats["pairs_elsewhere"].tolist() == elsewhere.tolist()
        assert 0 < int(elsewhere.sum()) < 8 * cfg.top_k * B * S
    worst = worst_leaf(ours.grads, theirs.grads)
    assert len(worst) == len(jax.tree.leaves(ours.params)) - 1
    assert max(worst.values()) < 2e-4, sorted(worst.items(), key=lambda kv: -kv[1])[:3]


@pytest.mark.parametrize("wrong", list(mellum2_wrong.VARIANTS))
def test_one_thing_wrong_moves_the_tiny_loss_or_gradient(wrong):
    """The cell's one-thing-wrong table (chipbench/tools/mellum2_wrong.py:
    the same patches of the reference), at the tiny size in float32 over
    one period: each row moves the loss or some leaf's gradient beyond the
    train path's own tolerances (2e-6 and 2e-4, which the program meets:
    `test_train_path_meets_the_reference_in_loss_and_gradients`), so a
    program that computed so would fail here."""
    cfg = dataclasses.replace(M_FP32, n_layers=4)
    sound = reference_path(MELLUM2, cfg)
    params, batch, shape = seeded_params(MELLUM2, cfg), MELLUM2.batch_of(cfg), MELLUM2.shape_of(cfg)

    def f(p):   # a function a case: `jax.jit` keeps one trace a function, patched or not
        return mellum2_decoder.loss_parts(p, batch["tokens"], batch["targets"], shape)["loss"]

    with mellum2_wrong.VARIANTS[wrong](), MELLUM2.reference_set_up(), \
            jax.default_matmul_precision("highest"):
        moved = abs(float(jax.jit(f)(params)) / float(sound.parts["loss"]) - 1)
        if wrong not in mellum2_wrong.PRECISION_ONLY:
            # a mechanism is no rounding: it moves the loss a hundred tolerances (2.6e-4 to
            # 1.5e-2 here), and the forward alone says so
            assert moved > 2e-4, moved
            return
        # a precision may leave the loss where it was by luck (3.6e-7 here): the gradient
        grads = jax.jit(jax.grad(f))(params)
    worst = max(worst_leaf(jax.tree.map(lambda g: g.astype(jnp.float32), grads),
                           sound.grads).values())
    assert moved > 2e-6 or worst > 2e-4, (moved, worst)


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    LAGUNA, MELLUM2)
