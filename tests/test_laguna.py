"""The typed stack (models/laguna.py) through the one decoder, at a small
size on the CPU, seeded weights, each of its two models against its own
plain reference: Laguna (PR 39; chipbench/reference/laguna_decoder.py)
and Mellum2 (PR 53; chipbench/reference/mellum2_decoder.py: one head
count, a norm a head, no gate, yarn on the whole head, no dense layer, no
shared expert), as cases of the same tests where the stack is shared: the attention
sublayer of both kinds (the window, YaRN on part of a head, the gate),
softmax top-k routing with its bias, renormalisation and scaling, a tail
after the last whole period, the shares that add up. (The flash kernels
under a window: tests/test_flash_window.py; the whole train path over a
dense layer and two periods in loss and gradients, Mellum2's
one-thing-wrong table, remat and bf16: tests/test_contract_laguna.py;
`config_from_hf` and the engine's refusal: tests/test_model_contract.py.)"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import laguna_decoder, mellum2_decoder
from chipbench.tools import mellum2_wrong
from model_cases import LAGUNA, MELLUM2, seeded_params, worst_leaf
from ray_tpu.models import laguna, llama, moe
from ray_tpu.models.registry import config_from_hf
from ray_tpu.nn import layers as nn_layers
from ray_tpu.nn.layers import rms_norm

FP32, B, S = LAGUNA.fp32, LAGUNA.batch, LAGUNA.seq
M_FP32 = MELLUM2.fp32


def block_of(params, position, period=0, bias_row=None):
    lp = jax.tree.map(lambda w: w[period], params["layers"]["period"][str(position)])
    if bias_row is not None:
        lp["router_bias"] = params["layers"]["router_bias"][bias_row]
    return lp


def tables_of(cfg, s):
    pos = jnp.arange(s)
    return {laguna.FULL: cfg.rope_full.tables(cfg.head_dim, pos),
            laguna.SLIDING: cfg.rope_sliding.tables(cfg.head_dim, pos)}


# -- the stack's plan and the tree ----------------------------------------------------


def test_the_stack_is_cut_into_a_dense_layer_whole_periods_and_a_tail():
    full = laguna.plan(laguna.LAGUNA_S_2_1)
    assert full["dense"] == (laguna.FULL, 48) and full["periods"] == 11
    assert full["period"] == [(laguna.SLIDING, 72)] * 3 + [(laguna.FULL, 48)]
    assert full["tail"] == [(laguna.SLIDING, 72)] * 3
    cell = dataclasses.replace(laguna.LAGUNA_S_2_1, n_layers=5, vocab_size=12544, experts_held=8)
    assert laguna.plan(cell)["periods"] == 1 and not laguna.plan(cell)["tail"]
    assert cell.num_params() == 811_018_240   # ISSUE 39's table: 811.0M
    tiny = laguna.plan(FP32)
    assert tiny["periods"] == 2 and len(tiny["period"]) == 4 and not tiny["tail"]
    # the tree, its axes and the count agree, with a tail too
    for cfg in (FP32, dataclasses.replace(FP32, n_layers=11)):
        params = llama.init_params(cfg, jax.random.key(0))
        axes = jax.tree.map(lambda a: 0, llama.logical_axes(cfg),
                            is_leaf=lambda x: isinstance(x, tuple))
        assert jax.tree.structure(params) == jax.tree.structure(axes)
        assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
        assert params["layers"]["router_bias"].shape == (cfg.n_expert_layers, cfg.n_experts)
    assert set(params["layers"]) == {"router_bias", "period", "tail"}
    assert params["layers"]["period"]["0"]["wq"].shape == (2, 64, 6 * 16)
    assert params["layers"]["period"]["3"]["wq"].shape == (2, 64, 4 * 16)
    assert params["layers"]["tail"]["1"]["wg"].shape == (64, 6)


def test_a_model_of_one_head_count_is_cut_into_periods_by_type_alone():
    """Mellum2 through the same plan: no dense layer, `heads_per_layer`
    empty (32 everywhere), the period of four found from the types; the
    tree has no gate and a norm a head; the counts are ISSUE 53's (its
    table leaves the 4 x 64 selection biases out)."""
    full = laguna.plan(laguna.MELLUM2_12B_A2_5B)
    assert full["dense"] is None and full["periods"] == 7 and not full["tail"]
    assert full["period"] == [(laguna.SLIDING, 32)] * 3 + [(laguna.FULL, 32)]
    assert laguna.MELLUM2_12B_A2_5B.num_params() == 12_149_924_864   # 12.15B, "12B"
    cell = dict(n_layers=4, vocab_size=24576, experts_held=16)
    rung_a = dataclasses.replace(laguna.MELLUM2_12B_A2_5B, **cell)
    rung_b = dataclasses.replace(rung_a, vocab_size=12288, experts_held=8)
    assert laguna.plan(rung_a)["periods"] == 1 and not laguna.plan(rung_a)["tail"]
    assert rung_a.num_params() - 4 * 64 == 595_154_176
    assert rung_b.num_params() - 4 * 64 == 340_350_208
    # 2.44B a token: the name's "A2.5B" (attention, router, 8 experts, both tables)
    active = dataclasses.replace(laguna.MELLUM2_12B_A2_5B, experts_held=8).num_params()
    assert round(active / 1e9, 2) == 2.44
    for cfg in (M_FP32, dataclasses.replace(M_FP32, n_layers=6)):   # a tail of two sliding
        params = llama.init_params(cfg, jax.random.key(0))
        axes = jax.tree.map(lambda a: 0, llama.logical_axes(cfg),
                            is_leaf=lambda x: isinstance(x, tuple))
        assert jax.tree.structure(params) == jax.tree.structure(axes)
        assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
        assert "dense_layers" not in params
    assert set(params["layers"]) == {"router_bias", "period", "tail"}
    block = params["layers"]["period"]["3"]
    assert "wg" not in block and "shared_up" not in block
    assert block["wq"].shape == (1, 64, 8 * 16) and block["wk"].shape == (1, 64, 2 * 16)
    assert block["q_norm"].shape == block["k_norm"].shape == (1, 16)
    assert params["layers"]["tail"]["1"]["q_norm"].shape == (16,)


# -- the attention sublayer against the reference ----------------------------------------


@pytest.mark.parametrize("model,position,kind,heads,impl", [
    (LAGUNA, 0, laguna.SLIDING, 6, "xla"), (LAGUNA, 3, laguna.FULL, 4, "xla"),
    (LAGUNA, 0, laguna.SLIDING, 6, "flash"), (LAGUNA, 3, laguna.FULL, 4, "flash"),
    # Mellum2 through the kernels alone: the train path's cases hold its `xla` form whole
    (MELLUM2, 0, laguna.SLIDING, 8, "flash"), (MELLUM2, 3, laguna.FULL, 8, "flash")],
    ids=lambda v: getattr(v, "name", None))
def test_attention_sublayer_is_the_references(model, position, kind, heads, impl):
    """h -> h + (gated) attention of one kind: the window or not, the whole
    head rotated or YaRN on half of it (Laguna) or on all of it past its
    original length (Mellum2), a norm a head or none, groups of 4 query
    heads a key head, forward and the gradients of the input and of every
    weight."""
    cfg = dataclasses.replace(model.fp32, attention_impl=impl)
    lp = block_of(seeded_params(model, cfg), position)
    B, S = model.batch, model.seq
    h = jax.random.normal(jax.random.key(3), (B, S, cfg.d_model), jnp.float32)
    shape = model.shape_of(cfg)

    def program(h, lp):
        return laguna.attention_sublayer(h, rms_norm(h, lp["ln1"], cfg.rms_eps), lp, cfg, kind=kind,
                                         heads=heads, tables=tables_of(cfg, S), segment_ids=None)

    def reference(h, lp):
        if model is MELLUM2:
            with model.reference_set_up():
                return jnp.stack([mellum2_decoder.attention(h[b], lp, shape, kind)
                                  for b in range(B)])
        return jnp.stack([laguna_decoder.attention(h[b], lp, shape, kind, heads) for b in range(B)])

    probe = jax.random.normal(jax.random.key(4), h.shape)

    def value_and_grads(f):
        return jax.jit(jax.value_and_grad(
            lambda h, lp: (lambda out: ((out * probe).sum(), out))(f(h, lp)), (0, 1), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, out), got = value_and_grads(program)(h, lp)
        (_, ref), want = value_and_grads(reference)(h, lp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    used = [k for k in ("ln1", "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo") if k in lp]
    assert ("wg" in used) == (model is LAGUNA) and ("q_norm" in used) == (model is MELLUM2)
    worst = worst_leaf({"h": got[0], **{k: got[1][k] for k in used}},
                       {"h": want[0], **{k: want[1][k] for k in used}})
    assert max(worst.values()) < 1e-4, worst


def test_a_token_600_back_reaches_a_full_layer_and_not_a_sliding_one():
    """The window by itself, at the published 512: changing the input at
    position 0 changes a sliding layer's output at positions 0 .. 511
    and nowhere after; a full layer's, everywhere."""
    cfg = dataclasses.replace(FP32, sliding_window=512)
    params = seeded_params(LAGUNA, cfg)
    s = 640
    h = jax.random.normal(jax.random.key(3), (1, s, cfg.d_model), jnp.float32)
    moved = h.at[0, 0].add(1.0)
    for position, kind, heads in ((0, laguna.SLIDING, 6), (3, laguna.FULL, 4)):
        lp = block_of(params, position)

        def run(h):
            return laguna.attention_sublayer(
                h, rms_norm(h, lp["ln1"], cfg.rms_eps), lp, cfg, kind=kind, heads=heads,
                tables=tables_of(cfg, s), segment_ids=None) - h

        diff = np.abs(np.asarray(run(moved) - run(h))).max(axis=-1)[0]
        assert diff[:512].min() > 0
        if kind == laguna.SLIDING:
            assert diff[512:].max() == 0.0
        else:
            assert diff[512:].min() > 0


def test_the_gate_is_one_sigmoid_a_head_and_token_on_the_attentions_output():
    lp = block_of(seeded_params(LAGUNA, FP32), 0)
    h = jax.random.normal(jax.random.key(3), (B, S, FP32.d_model), jnp.float32)

    def run(lp):
        x = rms_norm(h, lp["ln1"], FP32.rms_eps)
        return x, laguna.attention_sublayer(h, x, lp, FP32, kind=laguna.SLIDING, heads=6,
                                            tables=tables_of(FP32, S), segment_ids=None) - h

    with jax.default_matmul_precision("highest"):
        x, gated = run(lp)
        # a gate of 1/2 everywhere (wg = 0) halves what an open gate would give
        _, half = run({**lp, "wg": jnp.zeros_like(lp["wg"])})
        # head 2's gate shut (a large negative logit): its columns of wo see nothing
        shut = lp["wg"].at[:, 2].set(-1e4 * jnp.sign(x[0, 0]) / FP32.d_model ** 0.5)
        _, without = run({**lp, "wg": shut.at[:, 2].set(-1e4 * jnp.ones_like(shut[:, 2]))})
    wo = lp["wo"].reshape(6, 16, -1)
    assert float(jnp.abs(gated - half).max()) > 1e-3
    g = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, lp["wg"]))
    assert g.shape == (B, S, 6)
    # gated = sum_h g_h o_h wo_h and half = sum_h o_h wo_h / 2: with one head's wo alone they
    # differ by the factor 2 g_h
    for head in (0, 5):
        only = {**lp, "wo": jnp.zeros_like(wo).at[head].set(wo[head]).reshape(lp["wo"].shape)}
        with jax.default_matmul_precision("highest"):
            _, a = run(only)
            _, b = run({**only, "wg": jnp.zeros_like(lp["wg"])})
        np.testing.assert_allclose(np.asarray(a), np.asarray(2 * g[..., head:head + 1] * b),
                                   rtol=1e-4, atol=1e-6)
    assert np.isfinite(np.asarray(without)).all()


def test_yarn_is_hfs_function_and_turns_half_a_head():
    """nn/layers.py::yarn_inv_freq against the reference's line-for-line
    transcription of `_compute_yarn_parameters` at the published
    parameters, the numbers ISSUE 39 spells out, and `rotate_head_major`
    against the slices and concatenation it stands for."""
    r = laguna.LAGUNA_S_2_1.rope_full
    got = nn_layers.yarn_inv_freq(64, r.theta, r.factor, r.original_max, r.beta_fast, r.beta_slow)
    want = laguna_decoder.yarn_parameters(64, 5e5, 128, 8192, 32, 1)
    np.testing.assert_array_equal(got, want)
    pair = lambda turns: 64 * math.log(8192 / (turns * 2 * math.pi)) / (2 * math.log(5e5))  # noqa: E731
    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (9, 18)
    base = 1.0 / 5e5 ** (np.arange(0, 64, 2, dtype=np.float32) / 64)
    np.testing.assert_allclose(got[:low + 1], base[:low + 1], rtol=1e-6)       # extrapolated: kept
    np.testing.assert_allclose(got[high:], base[high:] / 128, rtol=1e-6)       # interpolated
    assert (got[low + 1:high] < base[low + 1:high]).all()
    assert (got[low + 1:high] > base[low + 1:high] / 128).all()
    x = jax.random.normal(jax.random.key(0), (2, 3, 16, 128))
    cos, sin = r.tables(128, jnp.arange(16))
    assert cos.shape == (1, 16, 32)
    np.testing.assert_allclose(np.asarray(cos[0, 0]), r.attention_factor, rtol=1e-6)
    c, s = cos[:, None], sin[:, None]
    x1, x2 = x[..., :32], x[..., 32:64]
    literal = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., 64:]], axis=-1)
    np.testing.assert_allclose(np.asarray(nn_layers.rotate_head_major(x, cos, sin)),
                               np.asarray(literal), rtol=1e-6, atol=1e-6)
    got_g = jax.grad(lambda x: (nn_layers.rotate_head_major(x, cos, sin) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(2 * r.attention_factor ** 2 * x.at[
        ..., 64:].multiply(1 / r.attention_factor ** 2)), rtol=1e-4, atol=1e-5)
    # the whole head (a sliding layer's) is `apply_rope_head_major` on tables of positions
    cos_w, sin_w = laguna.LAGUNA_S_2_1.rope_sliding.tables(128, jnp.arange(16))
    table = nn_layers.rope_frequencies(128, 16, 10000.0)
    np.testing.assert_allclose(
        np.asarray(nn_layers.rotate_head_major(x, cos_w, sin_w)),
        np.asarray(nn_layers.apply_rope_head_major(x, *table, jnp.arange(16))),
        rtol=1e-5, atol=1e-5)


def test_yarn_on_a_whole_head_past_its_original_length():
    """Mellum2's full layers: yarn on all 128 channels (64 pairs, where
    Laguna's full layers turn 32), factor 16 over 8,192, at positions up to
    16,383: twice the original length, where the plain table would be out
    of range and `laguna-train` (positions 0-4,095) never looks."""
    r = laguna.MELLUM2_12B_A2_5B.rope_full
    assert (r.partial, r.factor, r.original_max) == (1.0, 16.0, 8192)
    assert r.attention_factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    got = nn_layers.yarn_inv_freq(128, r.theta, r.factor, r.original_max, r.beta_fast, r.beta_slow)
    np.testing.assert_array_equal(got, mellum2_decoder.yarn_parameters(128, 5e5, 16, 8192, 32, 1))
    pair = lambda turns: 128 * math.log(8192 / (turns * 2 * math.pi)) / (2 * math.log(5e5))  # noqa: E731
    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (18, 35)   # twice Laguna's 9 and 18: the ramp is by the pair's share
    base = 1.0 / 5e5 ** (np.arange(0, 128, 2, dtype=np.float32) / 128)
    np.testing.assert_allclose(got[:low + 1], base[:low + 1], rtol=1e-6)       # extrapolated: kept
    np.testing.assert_allclose(got[high:], base[high:] / 16, rtol=1e-6)        # interpolated
    # the ramp computed on 64 channels (the one-thing-wrong table's row) is another table
    half = mellum2_wrong.yarn_ramp_on_half_the_head(128, 5e5, 16, 8192, 32, 1)
    assert np.abs(half / got - 1).max() > 0.5
    # the program's tables at the cell's positions are the reference's, to float32's rounding
    # of an angle of thousands of radians, and not the plain rotary's
    pos = jnp.arange(16384)
    cos, sin = r.tables(128, pos)
    ref_cos, ref_sin = mellum2_decoder.rope_tables(
        MELLUM2.shape_of(laguna.MELLUM2_12B_A2_5B)["rope_parameters"][laguna.FULL], 128, 16384)
    assert cos.shape == (1, 16384, 64)
    np.testing.assert_allclose(np.asarray(cos[0]), np.asarray(ref_cos), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin[0]), np.asarray(ref_sin), atol=2e-3)
    plain_cos, _ = laguna.MELLUM2_12B_A2_5B.rope_sliding.tables(128, pos)
    late = np.abs(np.asarray(cos[0, 8192:, high:]) - np.asarray(plain_cos[0, 8192:, high:]))
    assert late.max() > 0.25   # the interpolated pairs turn 16 times slower
    np.testing.assert_allclose(np.asarray(cos[0, :, 0]) ** 2 + np.asarray(sin[0, :, 0]) ** 2,
                               r.attention_factor ** 2, rtol=1e-5)
    # all 128 channels turn: nothing passes through
    x = jax.random.normal(jax.random.key(0), (1, 2, 16, 128))
    c, s = cos[:, None, 9000:9016], sin[:, None, 9000:9016]
    lo, hi = x[..., :64], x[..., 64:]
    literal = jnp.concatenate([lo * c - hi * s, hi * c + lo * s], -1)
    np.testing.assert_allclose(np.asarray(nn_layers.rotate_head_major(x, c[:, 0], s[:, 0])),
                               np.asarray(literal), rtol=1e-6, atol=1e-6)


# -- the router -----------------------------------------------------------------------


@pytest.mark.parametrize("model,bias", [(LAGUNA, 0.0), (LAGUNA, 0.05), (MELLUM2, 0.05)],
                         ids=["laguna-zero_bias", "laguna-random_bias", "mellum2-random_bias"])
def test_softmax_routing_bias_renormalisation_and_scaling(model, bias):
    """moe_ffn on a block of either kind against its reference's expert
    half: top-k of p + b, weights 2.5 x p / sum p and the shared expert
    (Laguna) or p / sum p and none (Mellum2); and the old softmax
    configurations keep what they had."""
    FP32, B, S, decoder = model.fp32, model.batch, model.seq, model.reference
    params = seeded_params(model, FP32, bias=bias)
    lp = block_of(params, 1, bias_row=1)
    x = jax.random.normal(jax.random.key(5), (B, S, FP32.d_model), jnp.float32)
    shape = model.shape_of(FP32)
    assert (FP32.shared_d_ff, FP32.routed_scaling) == ((32, 2.5) if model is LAGUNA else (0, 1.0))
    with jax.default_matmul_precision("highest"):
        out, stats, _ = moe.moe_ffn(x, lp, FP32)
        want = []
        for b in range(B):
            weights = decoder.route(x[b], lp, shape)
            routed = sum(weights[:, e:e + 1] * decoder._swiglu(
                x[b], lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
                for e in range(FP32.n_experts))
            if FP32.shared_d_ff:
                routed = routed + decoder._swiglu(
                    x[b], lp["shared_gate"], lp["shared_up"], lp["shared_down"])
            want.append(routed)
            np.testing.assert_allclose(np.asarray(weights.sum(-1)), FP32.routed_scaling, rtol=1e-5)
            assert ((weights > 0).sum(-1) == FP32.top_k).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.stack(want)), rtol=2e-5, atol=2e-5)
    assert int(stats["tokens_per_expert"].sum()) == FP32.top_k * B * S
    if bias:
        plain, _, _ = moe.moe_ffn(x, {k: v for k, v in lp.items() if k != "router_bias"}, FP32)
        assert float(jnp.abs(plain - out).max()) > 1e-4   # the bias moved some choice


# -- a tail after the last whole period ---------------------------------------------------


def test_a_tail_after_the_last_whole_period_runs_in_layer_order():
    cfg = dataclasses.replace(FP32, n_layers=11)   # dense + 2 periods + sliding, sliding
    params, batch = seeded_params(LAGUNA, cfg, bias=0.05), LAGUNA.batch_of(cfg)
    shape = LAGUNA.shape_of(cfg)
    with jax.default_matmul_precision("highest"):
        loss, _, stats = jax.jit(lambda p: llama.loss_and_weight_fn(p, batch, cfg))(params)
        ref = laguna_decoder.loss_parts(params, batch["tokens"], batch["targets"], shape)
    assert float(loss) == pytest.approx(float(ref["loss"]), rel=2e-6)
    assert stats["tokens_per_expert"].tolist() == ref["tokens_per_expert"].tolist()


# -- the share adds up ---------------------------------------------------------------


def test_thirty_two_shares_add_up_to_the_uncut_layer():
    """The cell's deployment, small: 32 shares of 2 of 64 experts, top-10
    softmax with a bias, weights renormalised x 2.5. The router and the
    shared expert are computed alike on every chip and counted ONCE; the
    shares' routed outputs, so counted, sum to the uncut layer's, and so
    do the gradients of the input; every share counts what the uncut
    layer counts, and what one computes the others count as elsewhere."""
    whole = dataclasses.replace(FP32, n_experts=64, top_k=10)
    key = jax.random.key(2)
    lp = jax.tree.map(lambda w: w[0], moe.expert_params(dataclasses.replace(whole, n_layers=1), key))
    lp["router_bias"] = 0.01 * jax.random.normal(jax.random.key(3), (64,))
    x = jax.random.normal(jax.random.key(5), (B, S, whole.d_model), jnp.float32)
    no_shared = {**lp, "shared_down": jnp.zeros_like(lp["shared_down"])}
    experts = ("w_gate", "w_up", "w_down")

    def run(cfg, lp):   # bare: 32 shares are 32 configurations, a compile each under jit
        out, vjp, stats = jax.vjp(lambda x: moe.moe_ffn(x, lp, cfg)[:2], x, has_aux=True)
        return out, vjp(jnp.ones_like(out))[0], stats

    def share(first, lp):
        cfg = dataclasses.replace(whole, experts_held=2, first_expert_held=first)
        return run(cfg, {**lp, **{k: lp[k][first:first + 2] for k in experts}})

    with jax.default_matmul_precision("highest"):
        full = run(whole, lp)
        shared_alone = run(whole, {**lp, "w_down": jnp.zeros_like(lp["w_down"])})
        router_only = run(whole, {**no_shared, "w_down": jnp.zeros_like(lp["w_down"])})
        routed = [share(first, no_shared) for first in range(0, 64, 2)]
    for i in (0, 1):   # the output, and the gradient of the input
        total = sum(np.asarray(r[i]) for r in routed) + np.asarray(shared_alone[i])
        if i == 1:  # the router's own path to x is in every term: counted once
            total = total - 32 * np.asarray(router_only[1])
        np.testing.assert_allclose(total, np.asarray(full[i]), rtol=2e-5, atol=2e-5)
    counts = full[2]["tokens_per_expert"]
    assert int(counts.sum()) == 10 * B * S
    for first, (_, _, stats) in zip(range(0, 64, 2), routed):
        assert stats["tokens_per_expert"].tolist() == counts.tolist()
        assert int(stats["pairs_elsewhere"]) == int(counts.sum() - counts[first:first + 2].sum())
        assert int(stats["dropped_pairs"]) == 0


def test_the_eight_shares_of_mellum2s_layer_add_up_to_the_uncut_layer():
    """The cell's deployment (ISSUE 53's rung (b)), small: 64 experts, top-8
    softmax with a bias, weights renormalised, no shared expert, over 8
    chips of 8 experts (rung (a)'s 16 of 64 would sit on `held_rows_bound`'s
    own edge, 2 C = the pairs, and still be built compact). The router is
    computed alike on every chip and counted ONCE; the shares' outputs sum
    to the uncut layer's, and so do the gradients of the input; every
    share counts what the uncut layer counts."""
    whole, held = dataclasses.replace(M_FP32, n_experts=64, top_k=8), 8
    B, S = MELLUM2.batch, MELLUM2.seq
    lp = jax.tree.map(lambda w: w[0], moe.expert_params(dataclasses.replace(whole, n_layers=1),
                                                        jax.random.key(2)))
    lp["router_bias"] = 0.01 * jax.random.normal(jax.random.key(3), (64,))
    x = jax.random.normal(jax.random.key(5), (B, S, whole.d_model), jnp.float32)
    experts = ("w_gate", "w_up", "w_down")
    assert moe.held_rows_bound(B * S * 8, 8, 64) == 512   # C: 2 x a uniform router's 128 rows
    assert moe.held_rows_bound(B * S * 8, 16, 64) == B * S * 8 // 2
    assert moe.held_rows_bound(16384 * 8, 8, 64) == 32768   # the cell's C

    def run(cfg, lp):
        out, vjp, stats = jax.vjp(lambda x: moe.moe_ffn(x, lp, cfg)[:2], x, has_aux=True)
        return out, vjp(jnp.ones_like(out))[0], stats

    def share(first):
        cfg = dataclasses.replace(whole, experts_held=held, first_expert_held=first)
        return run(cfg, {**lp, **{k: lp[k][first:first + held] for k in experts}})

    with jax.default_matmul_precision("highest"):
        full = run(whole, lp)
        router_only = run(whole, {**lp, "w_down": jnp.zeros_like(lp["w_down"])})
        shares = [share(first) for first in range(0, 64, held)]
    n = len(shares)
    np.testing.assert_allclose(sum(np.asarray(r[0]) for r in shares), np.asarray(full[0]),
                               rtol=2e-5, atol=2e-5)
    # the router's own path to x is in every share's gradient: counted once
    np.testing.assert_allclose(sum(np.asarray(r[1]) for r in shares)
                               - (n - 1) * np.asarray(router_only[1]), np.asarray(full[1]),
                               rtol=2e-5, atol=2e-5)
    counts = full[2]["tokens_per_expert"]
    assert int(counts.sum()) == 8 * B * S
    for first, (_, _, stats) in zip(range(0, 64, held), shares):
        assert stats["tokens_per_expert"].tolist() == counts.tolist()
        assert int(stats["pairs_elsewhere"]) == int(counts.sum() - counts[first:first + held].sum())
        assert int(stats["dropped_pairs"]) == 0 and "compact" in stats


# -- the registry ------------------------------------------------------------------


def test_other_families_still_refuse_a_scaled_rotary_and_an_explicit_head_dim():
    llama_like = {"architectures": ["LlamaForCausalLM"], "vocab_size": 100, "hidden_size": 64,
                  "num_hidden_layers": 2, "num_attention_heads": 4, "intermediate_size": 128}
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf({**llama_like, "rope_scaling": {"rope_type": "yarn", "factor": 4}})
    with pytest.raises(ValueError, match="head_dim"):
        config_from_hf({**llama_like, "head_dim": 32})
