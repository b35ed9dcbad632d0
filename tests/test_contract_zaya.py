"""ZAYA1's whole train path (PR 32) at a small size on the CPU, seeded
weights, against the plain reference (chipbench/reference/zaya_decoder.py):
loss, the tokens per expert of every layer and every gradient, with all
experts and with a share; and the contract's two compiled cases for the
same row (tests/model_cases.py), which read the same memo: this process
compiles the plain path once for both. The sublayers, causality and the
shares: tests/test_zaya.py."""

import dataclasses

import jax
import pytest

from model_cases import ZAYA, contract_cases, reference_path, train_path, worst_leaf

FP32, B, S = ZAYA.fp32, ZAYA.batch, ZAYA.seq


@pytest.mark.parametrize("held", [None, (2, 1)], ids=["all_experts", "a_share"])
def test_train_path_meets_the_reference_in_loss_and_gradients(held):
    """llama.loss_fn (the one train path) on a ZAYA1-kind configuration
    against the plain reference, on seeded weights and skewed tokens: the
    loss, the tokens per expert of every layer, and every gradient by
    its worst leaf."""
    cfg = FP32 if held is None else dataclasses.replace(
        FP32, experts_held=held[0], first_expert_held=held[1])
    ours, theirs = train_path(ZAYA, cfg), reference_path(ZAYA, cfg)
    loss, stats, ref = ours.loss, ours.stats, theirs.parts
    assert float(loss) == pytest.approx(float(ref["loss"]), rel=2e-6)
    assert stats["tokens_per_expert"].tolist() == ref["tokens_per_expert"].tolist()
    assert int(stats["dropped_pairs"].sum()) == 0
    if held is not None:
        first, n = held[1], held[0]
        elsewhere = B * S - stats["tokens_per_expert"][:, first:first + n].sum(-1)
        assert stats["pairs_elsewhere"].tolist() == elsewhere.tolist()
        assert 0 < int(elsewhere.sum()) < cfg.n_layers * B * S
    worst = worst_leaf(ours.grads, theirs.grads)   # the selection bias takes no gradient
    assert len(worst) == len(jax.tree.leaves(ours.params)) - 1
    assert max(worst.values()) < 2e-4, sorted(worst.items(), key=lambda kv: -kv[1])[:3]


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    ZAYA)
