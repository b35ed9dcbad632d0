"""The whole train path of Keye-VL-2.0's language model (PR 42) at a small
size on the CPU (the sequence past `indexer_topk`, so that the selection
bites), seeded weights, against the plain reference
(chipbench/reference/keye_decoder.py): loss, tokens per expert, selected
pairs and every gradient, with all experts and with a share, under a zero
and a random selection bias; no gradient into the indexer and none
through the selection, read from the same path; and the contract's two
compiled cases for the same row (tests/model_cases.py), which read the
same memo. The sublayer, the selected sets and the shares:
tests/test_keye.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import KEYE, contract_cases, reference_path, train_path
from ray_tpu.models import dsa

FP32, B, S = KEYE.fp32, KEYE.batch, KEYE.seq


def layer_of(params, l=0):
    return jax.tree.map(lambda w: w[l], params["layers"])


def test_no_gradient_reaches_the_indexer_and_none_passes_through_the_selection():
    cfg = FP32
    ours = train_path(KEYE, cfg)
    params, stats, grads = ours.params, ours.stats, ours.grads
    for n in ("idx_wq", "idx_wk", "idx_ww", "idx_norm_w", "idx_norm_b", "router_bias"):
        assert not np.asarray(grads["layers"][n]).any(), n
    assert np.asarray(grads["layers"]["wq"]).any()
    assert stats["dsa_selected"].tolist() == [B * (16 * 17 // 2 + 48 * 16)] * cfg.n_layers
    # the selection as a CONSTANT gives the sublayer's input the gradient it has with the indexer in
    lp = layer_of(params)
    x = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model), jnp.float32)
    pos = jnp.arange(S)
    sel = dsa.selection(x, lp, cfg, pos)

    def with_constant(x):
        real = dsa.selection
        try:
            dsa.selection = lambda *a: sel
            return dsa.dsa_sublayer(x, lp, cfg, positions=pos, segment_ids=None)[0].sum()
        finally:
            dsa.selection = real

    whole = jax.jit(jax.grad(
        lambda x: dsa.dsa_sublayer(x, lp, cfg, positions=pos, segment_ids=None)[0].sum()))(x)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(jax.jit(jax.grad(with_constant))(x)))


@pytest.mark.parametrize("bias", [0.0, 0.02], ids=["zero_bias", "random_bias"])
@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all_experts", "a_share"])
def test_train_path_meets_the_reference_in_loss_and_gradients(held, bias):
    cfg = FP32 if held is None else dataclasses.replace(
        FP32, first_expert_held=held[0], experts_held=held[1], vocab_size=256)
    ours, theirs = train_path(KEYE, cfg, bias), reference_path(KEYE, cfg, bias)
    loss, stats, grads, parts, want = ours.loss, ours.stats, ours.grads, theirs.parts, theirs.grads
    assert float(loss) == pytest.approx(float(parts["loss"]), rel=1e-5)
    assert stats["tokens_per_expert"].tolist() == parts["tokens_per_expert"].tolist()
    assert stats["dsa_selected"].tolist() == parts["selected_pairs"].tolist()
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        w = jax.tree_util.keystr(path)
        ref = np.asarray(jax.tree_util.tree_leaves_with_path(want)[[jax.tree_util.keystr(p) for p, _ in
               jax.tree_util.tree_leaves_with_path(want)].index(w)][1])
        np.testing.assert_allclose(np.asarray(g), ref, rtol=2e-4, atol=2e-6, err_msg=w)


# -- what every model holds: remat's gradients, bf16 near the reference ----------------

test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference = contract_cases(
    KEYE)
