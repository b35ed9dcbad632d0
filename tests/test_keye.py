"""The language model of Keye-VL-2.0 through the one decoder (PR 42), at a
small size on the CPU (the sequence past `indexer_topk`, so that the
selection bites), seeded weights, against the plain reference
(chipbench/reference/keye_decoder.py, imported): the attention sublayer
over the keys the indexer selects, the selected sets themselves, the tie
rule, `topk` >= T as the full GQA sublayer, what the `dots` policy keeps
of the indexer, the eight shares of a layer that add up, the train step
by the registry's name. (The flash kernels under a selection:
tests/test_flash_selection.py; no gradient into the indexer and none
through the selection, the whole train path in loss and gradients, remat,
flash and bf16: tests/test_contract_keye.py; `config_from_hf`, its refusals
and the engine's: tests/test_model_contract.py.)"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.reference import keye_decoder
from model_cases import KEYE, catalog_config, seeded_params
from ray_tpu.models import dsa, llama
from ray_tpu.models.registry import config_from_hf, get_model_config, list_models
from ray_tpu.nn.layers import rms_norm
from ray_tpu.ops.attention import attention_head_major
from ray_tpu.ops.flash import unpack_selection

FP32, B, S = KEYE.fp32, KEYE.batch, KEYE.seq


@pytest.fixture(autouse=True)
def blocks_of_queries(monkeypatch):
    monkeypatch.setattr(keye_decoder, "QUERY_BLOCK", 32)


def layer_of(params, l=0):
    return jax.tree.map(lambda w: w[l], params["layers"])


# -- the sublayer -----------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_attention_sublayer_is_the_references(impl):
    cfg = dataclasses.replace(FP32, attention_impl=impl)
    lp = layer_of(seeded_params(KEYE, cfg))
    h = jax.random.normal(jax.random.key(7), (B, S, cfg.d_model), jnp.float32)

    def ours(h):
        x = rms_norm(h, lp["ln1"], cfg.rms_eps)
        return h + dsa.dsa_sublayer(x, lp, cfg, positions=jnp.arange(S), segment_ids=None)[0]

    def theirs(h):
        shape = KEYE.shape_of(cfg)
        return jnp.stack([keye_decoder.attention(h[b], lp, shape)[0] for b in range(B)])

    probe = jax.random.normal(jax.random.key(8), h.shape)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda h: (ours(h) * probe).sum()))(h)
        want = jax.jit(jax.value_and_grad(lambda h: (theirs(h) * probe).sum()))(h)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-4, atol=1e-5)


def test_the_selected_sets_are_the_references():
    """Key for key: the bisection and the packed mask against the
    reference's stable sort, on the same index scores; 16 keys a query
    from row 16 on, every key before it up to there."""
    cfg = FP32
    lp = layer_of(seeded_params(KEYE, cfg))
    x = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        packed, n_selected, _ = dsa.selection(x, lp, cfg, jnp.arange(S))
        ours = np.asarray(unpack_selection(packed, S))
        J, c = cfg.indexer_heads, cfg.indexer_head_dim
        for b in range(B):
            q_i = keye_decoder._rope((x[b] @ lp["idx_wq"]).reshape(S, J, c), cfg.rope_theta)
            k_i = keye_decoder._rope(keye_decoder._layer_norm(
                x[b] @ lp["idx_wk"], lp["idx_norm_w"], lp["idx_norm_b"])[:, None],
                cfg.rope_theta)[:, 0]
            theirs = keye_decoder.select(keye_decoder.index_scores(q_i, k_i, x[b] @ lp["idx_ww"]),
                                         0, cfg.indexer_topk)
            assert (ours[b] == np.asarray(theirs)).all()
    per_row = ours.sum(-1)
    assert (per_row == np.minimum(np.arange(S) + 1, cfg.indexer_topk)).all()
    assert int(n_selected) == int(ours.sum()) == B * (16 * 17 // 2 + 48 * 16)
    assert not np.triu(ours[0], 1).any()  # nothing above the diagonal


def test_equal_scores_go_to_the_lower_key_and_are_counted():
    scores = jnp.asarray([[3.0, 1.0, 1.0, 1.0, 1.0, 0.5, -0.0, 0.0],
                          [5.0, 4.0, 3.0, 2.0, 1.0, 0.0, 9.0, 9.0],
                          [2.0, 2.0, 7.0, 7.0, 7.0, 7.0, 7.0, 7.0],
                          [-1.0, -2.0, -3.0, 0.0, 0.0, 0.0, 0.0, 0.0]])
    visible = jnp.asarray([[True] * 8, [True] * 6 + [False] * 2, [True] * 8, [True] * 3 + [False] * 5])
    chosen, ties = dsa.select_keys(scores, visible, 3)
    assert chosen.astype(int).tolist() == [[1, 1, 1, 0, 0, 0, 0, 0],    # the cut falls on 1.0: keys 1, 2
                                           [1, 1, 1, 0, 0, 0, 0, 0],    # unseen keys never, whatever they score
                                           [0, 0, 1, 1, 1, 0, 0, 0],    # three of the six 7.0s, the lowest
                                           [1, 1, 1, 0, 0, 0, 0, 0]]    # at most k visible: all of them
    assert ties.tolist() == [True, False, True, False]
    # -0.0 and 0.0 are one score: the lower key wins
    chosen, ties = dsa.select_keys(jnp.asarray([[0.0, -0.0, 0.0, -1.0]]), jnp.ones((1, 4), bool), 2)
    assert chosen.astype(int).tolist() == [[1, 1, 0, 0]] and ties.tolist() == [True]
    # the reference's stable sort agrees, row for row
    theirs = keye_decoder.select(jnp.where(visible, scores, -jnp.inf)[:, :8], 7, 3)
    assert (np.asarray(theirs)[[0, 2]] == np.asarray(dsa.select_keys(scores, visible, 3)[0])[[0, 2]]).all()


def test_topk_at_least_the_sequence_is_the_full_gqa_sublayer():
    cfg = dataclasses.replace(FP32, indexer_topk=S)
    lp = layer_of(seeded_params(KEYE, cfg))
    x = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model), jnp.float32)
    seen = {}

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v)
        return attention_head_major(q, k, v, **kw)

    with jax.default_matmul_precision("highest"):
        try:
            dsa.attention_head_major = spy
            out, stats = dsa.dsa_sublayer(x, lp, cfg, positions=jnp.arange(S), segment_ids=None)
        finally:
            dsa.attention_head_major = attention_head_major
        o = attention_head_major(seen["q"], seen["k"], seen["v"], causal=True)
        full = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].reshape(cfg.n_heads, cfg.head_dim, -1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), rtol=1e-6, atol=1e-6)
    assert int(stats["dsa_selected"]) == B * S * (S + 1) // 2 and int(stats["dsa_ties"]) == 0


# -- the model ----------------------------------------------------------------------


def test_the_dots_policy_keeps_the_selection_and_nothing_else_of_the_indexer():
    """What the backward of a rematerialised block reads of the indexer is
    the packed mask, saved under its name: in the compiled step no index
    score, no projection of the indexer and no second top-k is made again
    for the backward, and none is differentiated."""
    import re

    cfg = dataclasses.replace(FP32, remat=True, remat_policy="dots")
    params, batch = seeded_params(KEYE, cfg), KEYE.batch_of(cfg)
    grad = jax.jit(jax.grad(lambda p: llama.loss_and_weight_fn(p, batch, cfg)[0]))
    assert "dsa_sel" in str(jax.make_jaxpr(grad)(params))
    names = set(re.findall(r'op_name="([^"]*)"', grad.lower(params).compile().as_text()))
    indexer = [n for n in names if "dsa.select" in n or "dsa.index" in n]
    assert indexer and any("dsa.attend" in n and "rematted_computation" in n for n in names)
    assert not [n for n in indexer if "rematted_computation" in n or "transpose(" in n]


def test_eight_shares_add_up_to_the_uncut_layer():
    """The cell's deployment, small: 8 shares of 2 of 16 experts, top-8
    softmax with a bias, renormalised. The attention sublayer, indexer and
    all, and the router are computed alike on every chip and counted ONCE;
    the shares' routed outputs sum to the uncut layer's, and that is the
    uncut REFERENCE's whole layer; every share counts what the uncut
    layer counts, and what one computes the others count as elsewhere."""
    whole = dataclasses.replace(FP32, n_experts=16, top_k=8, n_layers=1)
    params = seeded_params(KEYE, whole, bias=0.01)
    lp = layer_of(params)
    h = jax.random.normal(jax.random.key(5), (B, S, whole.d_model), jnp.float32)
    experts = ("w_gate", "w_up", "w_down")

    def block(cfg, lp):
        def f(h):
            out, stats = llama._block(h, lp, config=cfg,
                                      positions=jnp.arange(S), segment_ids=None)
            return out, stats
        def both(h):
            out, vjp, stats = jax.vjp(f, h, has_aux=True)
            return out, vjp(jnp.ones_like(out))[0], stats
        return jax.jit(both)(h)

    def share(first):
        cfg = dataclasses.replace(whole, experts_held=2, first_expert_held=first)
        return block(cfg, {**lp, **{k: lp[k][first:first + 2] for k in experts}})

    with jax.default_matmul_precision("highest"):
        full = block(whole, lp)
        no_experts = block(whole, {**lp, "w_down": jnp.zeros_like(lp["w_down"])})
        shares = [share(first) for first in range(0, 16, 2)]
        theirs = []
        for b in range(B):
            mid, _ = keye_decoder.attention(h[b], lp, KEYE.shape_of(whole))
            theirs.append(keye_decoder.experts(mid, lp, KEYE.shape_of(whole))[0])
    for i in (0, 1):   # the output, and the gradient of the input
        # everything but the routed experts is in every share: counted once
        total = sum(np.asarray(s[i]) for s in shares) - 7 * np.asarray(no_experts[i])
        np.testing.assert_allclose(total, np.asarray(full[i]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(full[0]), np.asarray(jnp.stack(theirs)), rtol=2e-5, atol=2e-5)
    counts = full[2]["tokens_per_expert"]
    assert int(counts.sum()) == 8 * B * S
    for first, (_, _, stats) in zip(range(0, 16, 2), shares):
        assert stats["tokens_per_expert"].tolist() == counts.tolist()
        assert int(stats["pairs_elsewhere"]) == int(counts.sum() - counts[first:first + 2].sum())
        assert int(stats["dropped_pairs"]) == 0
        assert int(stats["dsa_selected"]) == int(full[2]["dsa_selected"])


def test_the_train_step_learns_a_batch_by_the_registrys_name():
    from ray_tpu.train.step import TrainState, make_train_step

    cfg = get_model_config("keye-tiny")
    assert isinstance(cfg, dsa.KeyeConfig) and "keye-vl-2.0-30b-a3b" in list_models()
    opt = optax.adamw(3e-3)
    state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
    before = jax.tree.map(np.asarray, {k: state.params["layers"][k] for k in ("idx_wq", "idx_ww")})
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    batch = KEYE.batch_of(cfg)
    losses = []
    for _ in range(12):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.6 * losses[0], losses
    assert m["stats"]["dsa_selected"].shape == (cfg.n_layers,) and "dsa_ties" in m["stats"]
    # no gradient moves the indexer: only AdamW's decay touches it (1e-4 x the rate a step)
    for k, w in before.items():
        np.testing.assert_allclose(np.asarray(state.params["layers"][k]), w, rtol=1e-4)


def test_packed_sequences_and_positions_by_row_are_refused_by_name():
    cfg = FP32
    params, batch = seeded_params(KEYE, cfg), KEYE.batch_of(cfg)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        llama.loss_and_weight_fn(params, {**batch, "segment_ids": jnp.zeros((B, S), jnp.int32)}, cfg)


def test_counts_of_parameters_and_operations_are_the_trees_and_the_issues():
    cfg = FP32
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(llama.init_params(cfg, jax.random.key(0))))
    full = get_model_config("keye-vl-2.0-30b-a3b")
    share = dataclasses.replace(full, n_layers=6, vocab_size=19072, experts_held=16)
    assert round(share.num_params() / 1e6, 1) == 659.5          # ISSUE 42: six layers of the share
    assert round(full.num_params() / 1e9, 1) == 30.6
    assert full.selected_keys(8192) == 1792.125 and full.selected_keys(2048) == 1024.5


# -- the registry ------------------------------------------------------------------


def test_config_from_hf_reads_the_language_models_keys_under_text_config():
    """As a checkpoint's config nests them."""
    nested = {"model_type": "KeyeVL2", "text_config": catalog_config(KEYE)}
    assert config_from_hf(nested) == get_model_config("keye-vl-2.0-30b-a3b")


def test_no_other_configuration_loads_the_module():
    """`ray_tpu.models.registry` knows the names and loads models/dsa.py
    (and with it the Pallas kernels' module) only when one is asked for."""
    import subprocess
    import sys

    code = ("import sys; from ray_tpu.models import llama, registry; "
            "c = registry.get_model_config('olmoe-1b-7b'); llama.logical_axes(c); "
            "assert 'ray_tpu.models.dsa' not in sys.modules; "
            "registry.get_model_config('keye-tiny'); assert 'ray_tpu.models.dsa' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
