"""GLM-4.7-Flash's whole train path (PR 34), the dense layer and the MTP
block with it, at a small size on the CPU, seeded weights, against the
plain reference (chipbench/reference/glm_lite_decoder.py): both losses,
the tokens per expert of every block and every gradient, with all experts
and with a share; MTP's shift on the same path's statistics; and the
contract's two compiled cases for the same row (tests/model_cases.py),
which read the same memo: this process compiles the plain path once for
all three. The sublayers, the routing and the shares:
tests/test_glm_lite.py."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import (GLM_LITE, contract_cases, reference_path, seeded_params, train_path,
                         worst_leaf)
from ray_tpu.models import llama
from ray_tpu.nn.layers import rms_norm

FP32, B, S = GLM_LITE.fp32, GLM_LITE.batch, GLM_LITE.seq


@pytest.mark.parametrize("held", [None, (4, 2)], ids=["all_experts", "a_share"])
def test_train_path_meets_the_reference_in_loss_and_gradients(held):
    """llama.loss_fn (the one train path) on a GLM-4.7-Flash-kind
    configuration, the dense layer and the MTP block with it, against the
    plain reference: both losses, the tokens per expert of every block
    (the MTP block's row last), and every gradient by its worst leaf."""
    cfg = FP32 if held is None else dataclasses.replace(
        FP32, experts_held=held[0], first_expert_held=held[1])
    ours, theirs = train_path(GLM_LITE, cfg), reference_path(GLM_LITE, cfg)
    loss, weight, stats, ref = ours.loss, ours.weight, ours.stats, theirs.parts
    assert float(weight) == B * S
    for name in ("loss_main", "loss_mtp"):
        assert float(stats[name]) == pytest.approx(float(ref[name]), rel=2e-6)
    assert float(loss) == pytest.approx(float(ref["loss"]), rel=2e-6)
    assert float(loss) == pytest.approx(
        float(stats["loss_main"]) + cfg.mtp_loss_weight * float(stats["loss_mtp"]), rel=1e-6)
    assert stats["tokens_per_expert"].shape == (cfg.n_expert_layers + 1, cfg.n_experts)
    assert stats["tokens_per_expert"].tolist() == ref["tokens_per_expert"].tolist()
    assert stats["tokens_per_expert"].sum(-1).tolist() == [cfg.top_k * B * S] * 3
    assert int(stats["dropped_pairs"].sum()) == 0
    if held is not None:
        first, n = held[1], held[0]
        elsewhere = cfg.top_k * B * S - stats["tokens_per_expert"][:, first:first + n].sum(-1)
        assert stats["pairs_elsewhere"].tolist() == elsewhere.tolist()
        assert 0 < int(elsewhere.sum()) < 3 * cfg.top_k * B * S
    worst = worst_leaf(ours.grads, theirs.grads)
    assert len(worst) == len(jax.tree.leaves(ours.params)) - 1
    assert max(worst.values()) < 2e-4, sorted(worst.items(), key=lambda kv: -kv[1])[:3]


# -- MTP's shift, and causality ---------------------------------------------------


@functools.partial(jax.jit, static_argnames="cfg")
def per_position_losses(params, batch, cfg):
    """(main nll [B, S], MTP nll [B, S - 1]) of the program's own path, by
    masking one position at a time out of neither: from the reference's
    per-position form on the program's hidden states."""
    h_last, _, block = llama._trunk(params, batch["tokens"], cfg)
    m, _ = llama.mtp_hidden(params, h_last, batch["targets"], cfg, block)
    head = params["lm_head"]

    def nll(h, norm, targets):
        lg = rms_norm(h, norm, cfg.rms_eps) @ head
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    main = nll(h_last, params["final_norm"], batch["targets"])
    ahead = nll(m[:, :-1], params["mtp"]["final_norm"], batch["targets"][:, 1:])
    return main, ahead


def test_mtp_predicts_the_token_after_next_and_the_last_position_weighs_nothing():
    """The MTP head's loss is the mean over S - 1 positions of the nll of
    t_{i+2} at position i; changing token t moves no MTP term before
    t - 2 (term i reads tokens up to i + 1 and the target t_{i+2}); the
    last position's logits reach no loss."""
    params, batch = seeded_params(GLM_LITE, FP32), GLM_LITE.batch_of(FP32)
    with jax.default_matmul_precision("highest"):
        stats = train_path(GLM_LITE, FP32).stats   # of these parameters and this batch
        main, ahead = per_position_losses(params, batch, FP32)
        assert float(stats["loss_mtp"]) == pytest.approx(float(ahead.mean()), rel=1e-5)
        assert float(stats["loss_main"]) == pytest.approx(float(main.mean()), rel=1e-5)
        # the sequence as ids 0 .. S: tokens are ids[:-1], targets ids[1:]; change id t
        t = 13
        ids = jnp.concatenate([batch["tokens"], batch["targets"][:, -1:]], axis=1)
        ids = ids.at[:, t].set((ids[:, t] + 7) % FP32.vocab_size)
        moved = {"tokens": ids[:, :-1], "targets": ids[:, 1:]}
        main2, ahead2 = per_position_losses(params, moved, FP32)
    # MTP term i reads ids 0 .. i + 1 and scores id i + 2: terms i <= t - 3 do not move
    np.testing.assert_allclose(np.asarray(ahead2[:, :t - 2]), np.asarray(ahead[:, :t - 2]),
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(ahead2[:, t - 2]), np.asarray(ahead[:, t - 2]), atol=1e-4)
    # the head's term i reads ids 0 .. i and scores id i + 1: terms i <= t - 2 do not move
    np.testing.assert_allclose(np.asarray(main2[:, :t - 1]), np.asarray(main[:, :t - 1]),
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(main2[:, t - 1]), np.asarray(main[:, t - 1]), atol=1e-4)
    # the last position: whatever its MTP logits are, the loss does not see them
    h_last, _, block = llama._trunk(params, batch["tokens"], FP32)
    m, _ = llama.mtp_hidden(params, h_last, batch["targets"], FP32, block)

    def mtp_loss_given(m):
        from ray_tpu.nn.layers import fused_cross_entropy_loss
        targets = batch["targets"]
        ahead_t = jnp.pad(targets[:, 1:], ((0, 0), (0, 1)))
        has = jnp.broadcast_to(jnp.arange(S) < S - 1, targets.shape)
        return fused_cross_entropy_loss(
            rms_norm(m, params["mtp"]["final_norm"], FP32.rms_eps), params["lm_head"],
            ahead_t, has)[0]

    gm_ = jax.grad(mtp_loss_given)(m)
    assert float(jnp.abs(gm_[:, -1]).max()) == 0.0 and float(jnp.abs(gm_[:, :-1]).max()) > 0.0


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    GLM_LITE)
