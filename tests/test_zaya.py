"""ZAYA1 through the one decoder (PR 32), at a small size on the CPU,
seeded weights, against the plain reference
(chipbench/reference/zaya_decoder.py, imported): the CCA sublayer
alone, the router's carried state, causality through both convolutions
and the value shift (and across a document boundary), the share of
experts that adds up, and the grouped matmul whose trailing rows no tile
visits. (The whole train path in loss and gradients, remat and bf16:
tests/test_contract_zaya.py; `config_from_hf` and the engine's refusal:
tests/test_model_contract.py.)"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import zaya_decoder
from model_cases import ZAYA, seeded_params
from ray_tpu.models import cca, llama, moe
from ray_tpu.nn.layers import rms_norm
from ray_tpu.ops import grouped_matmul as gm

FP32, B, S = ZAYA.fp32, ZAYA.batch, ZAYA.seq


def layer_of(params, i):
    return jax.tree.map(lambda x: x[i], params["layers"])


# -- the sublayers against the reference ---------------------------------------


@pytest.mark.parametrize("kernels", [(2, 2), (3, 2)])
def test_cca_sublayer_is_the_references(kernels):
    cfg = dataclasses.replace(FP32, conv_kernels=kernels)
    lp = layer_of(seeded_params(ZAYA, cfg), 1)
    h = jax.random.normal(jax.random.key(2), (B, S, cfg.d_model), jnp.float32)

    @jax.jit
    def program(h, lp):
        x = rms_norm(h, lp["ln1"], cfg.rms_eps)
        return h + cca.cca_sublayer(x, lp, cfg, positions=jnp.arange(S), segment_ids=None)

    @jax.jit
    def reference(h, lp):
        return jnp.stack([zaya_decoder.cca(h[b], lp, ZAYA.shape_of(cfg)) for b in range(B)])

    got = program(h, lp)
    with jax.default_matmul_precision("highest"):
        want = reference(h, lp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_router_state_is_carried_through_three_layers():
    """moe_ffn three times, each handed the state the one before gave:
    the states and the counts are the reference's, and the carried
    state matters (without it the third layer's state is another)."""
    cfg, shape = FP32, ZAYA.shape_of(FP32)
    params = seeded_params(ZAYA, cfg)
    x = jax.random.normal(jax.random.key(3), (1, S, cfg.d_model), jnp.float32)
    state, ref_state = None, jnp.zeros((S, cfg.router_hidden))
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            lp = layer_of(params, i)
            out, stats, state = moe.moe_ffn(x, lp, cfg, state)
            weights, _, ref_state = zaya_decoder.route(x[0], lp, shape, ref_state)
            np.testing.assert_allclose(np.asarray(state[0]), np.asarray(ref_state),
                                       rtol=1e-5, atol=1e-5)
            assert stats["tokens_per_expert"].tolist() == (weights > 0).sum(0).tolist()
            assert int(stats["tokens_per_expert"].sum()) == S and "pairs_elsewhere" not in stats
    fresh = moe.moe_ffn(x, layer_of(params, 2), cfg, None)[2]
    assert not np.allclose(np.asarray(fresh), np.asarray(state), atol=1e-3)


def test_top1_weight_is_the_probability_and_the_bias_only_chooses():
    cfg = FP32
    lp = layer_of(seeded_params(ZAYA, cfg), 0)
    x = jax.random.normal(jax.random.key(4), (1, S, cfg.d_model), jnp.float32)
    plain = moe.moe_ffn(x, {**lp, "router_bias": jnp.zeros(cfg.n_experts)}, cfg)[1]
    # a bias that forces expert 3: every token goes there, weighted by p_3 < 1
    forced, stats, _ = moe.moe_ffn(
        x, {**lp, "router_bias": jnp.zeros(cfg.n_experts).at[3].set(2.0)}, cfg)
    assert stats["tokens_per_expert"].tolist() == [0, 0, 0, S]
    assert plain["tokens_per_expert"].tolist() != [0, 0, 0, S]
    with jax.default_matmul_precision("highest"):
        _, probs, _ = zaya_decoder.route(x[0], lp, ZAYA.shape_of(cfg),
                                         jnp.zeros((S, cfg.router_hidden)))
        y = (jax.nn.silu(x[0] @ lp["w_gate"][3]) * (x[0] @ lp["w_up"][3])) @ lp["w_down"][3]
    np.testing.assert_allclose(np.asarray(forced[0]), np.asarray(probs[:, 3:4] * y),
                               rtol=1e-4, atol=1e-5)
    # and the bias takes no gradient
    g = jax.grad(lambda b: moe.moe_ffn(x, {**lp, "router_bias": b}, cfg)[0].sum())(
        lp["router_bias"])
    assert float(jnp.abs(g).max()) == 0.0


# -- causality, through both convolutions and the value shift ------------------


@functools.partial(jax.jit, static_argnames="cfg")
def hidden(params, tokens, cfg, segment_ids=None):
    return llama.hidden_states(params, tokens, cfg, segment_ids=segment_ids)


@pytest.mark.parametrize("kernels", [(2, 2), (3, 3)])
def test_changing_token_t_moves_nothing_before_t(kernels):
    cfg = dataclasses.replace(FP32, conv_kernels=kernels)
    params, tokens = seeded_params(ZAYA, cfg), ZAYA.batch_of(cfg)["tokens"]
    t = 11
    base = hidden(params, tokens, cfg)
    moved = hidden(params, tokens.at[:, t].set((tokens[:, t] + 7) % cfg.vocab_size), cfg)
    assert np.array_equal(np.asarray(base[:, :t]), np.asarray(moved[:, :t]))
    # and everything from t on does move: t itself, t + 1 through the shifts
    delta = np.abs(np.asarray(base - moved)).max(axis=-1)
    assert (delta[:, t:t + 3] > 1e-4).all()


@pytest.mark.parametrize("kernels", [(2, 2), (3, 3)])
def test_nothing_crosses_a_document_boundary(kernels):
    """Two documents packed in a row: the second's hidden states are
    those of the second document alone, whatever the first holds. The
    causal mask alone would pass the first's last tokens through the
    convolutions and the value shift."""
    cfg = dataclasses.replace(FP32, conv_kernels=kernels)
    params, tokens = seeded_params(ZAYA, cfg), ZAYA.batch_of(cfg)["tokens"]
    cut = 10
    segments = jnp.asarray(np.r_[np.zeros(cut), np.ones(S - cut)][None].repeat(B, 0), jnp.int32)
    packed = hidden(params, tokens, cfg, segments)
    alone = hidden(params, tokens[:, cut:], cfg)
    np.testing.assert_allclose(np.asarray(packed[:, cut:]), np.asarray(alone),
                               rtol=1e-5, atol=1e-5)
    other = tokens.at[:, :cut].set((tokens[:, :cut] + 3) % cfg.vocab_size)
    assert np.array_equal(np.asarray(hidden(params, other, cfg, segments)[:, cut:]),
                          np.asarray(packed[:, cut:]))
    # the mask is what does it: without segment ids the first document leaks
    assert not np.allclose(np.asarray(hidden(params, tokens, cfg)[:, cut:]),
                           np.asarray(alone), atol=1e-3)


def test_shift_tokens_is_zero_at_a_boundary_and_at_the_start():
    x = jnp.arange(1.0, 7.0).reshape(1, 6, 1)
    seg = jnp.asarray([[0, 0, 0, 1, 1, 1]])
    assert cca.shift_tokens(x, 1, None)[0, :, 0].tolist() == [0, 1, 2, 3, 4, 5]
    assert cca.shift_tokens(x, 1, seg)[0, :, 0].tolist() == [0, 1, 2, 0, 4, 5]
    assert cca.shift_tokens(x, 2, seg)[0, :, 0].tolist() == [0, 0, 1, 0, 0, 4]
    assert cca.shift_tokens(x, 0, seg) is x


@pytest.mark.parametrize("axis", [1, 2, 3])
def test_shift_tokens_shifts_along_the_axis_the_tokens_stand_on(axis):
    """The head-major sublayer holds its tokens on axis 2: the shift, its
    zero at the start and at a document's boundary (another one in each
    row) follow the axis, whatever else the array holds."""
    rows = jnp.arange(1.0, 13.0).reshape(2, 6)
    seg = jnp.asarray([[0, 0, 0, 1, 1, 1], [0, 0, 1, 1, 1, 1]])
    x = jnp.moveaxis(rows[:, :, None, None] * jnp.asarray([[1.0, -1.0], [2.0, 0.5], [3.0, 4.0]]),
                     1, axis)                                   # [2, 6, 3, 2] with the 6 on `axis`
    one = jnp.moveaxis(cca.shift_tokens(x, 1, seg, axis=axis), axis, 1)
    assert one[0, :, 0, 0].tolist() == [0, 1, 2, 0, 4, 5]
    assert one[1, :, 2, 1].tolist() == [0, 28, 0, 36, 40, 44]
    two = jnp.moveaxis(cca.shift_tokens(x, 2, seg, axis=axis), axis, 1)
    assert two[0, :, 1, 0].tolist() == [0, 0, 2, 0, 0, 8] and two[1, :, 0, 0].tolist() == [0, 0, 0, 0, 9, 10]
    unpacked = jnp.moveaxis(cca.shift_tokens(x, 1, None, axis=axis), axis, 1)
    assert unpacked[1, :, 0, 0].tolist() == [0, 7, 8, 9, 10, 11]
    assert cca.shift_tokens(x, 0, seg, axis=axis) is x


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_cca_sublayer_over_packed_documents_is_the_references(what):
    """Uneven documents, other ones in each row, four query heads a
    key-value head and taps (3, 2): the sublayer with `segment_ids`
    (positions restarting in every document) against the reference on
    each row with its `segments`, in value and in the gradients of a
    fixed cotangent with respect to the hidden state and every weight."""
    cfg = dataclasses.replace(FP32, n_heads=8, n_kv_heads=2, conv_kernels=(3, 2))
    assert cfg.n_heads // cfg.n_kv_heads == 4
    lp = layer_of(seeded_params(ZAYA, cfg), 1)
    lp = {k: lp[k] for k in ("ln1", *cca.attention_axes())}
    h = jax.random.normal(jax.random.key(8), (B, S, cfg.d_model), jnp.float32)
    ct = jax.random.normal(jax.random.key(9), (B, S, cfg.d_model), jnp.float32)
    seg = jnp.asarray([np.repeat([0, 1, 2], [5, 12, 7]), np.repeat([0, 1, 2, 3], [1, 9, 3, 11])],
                      jnp.int32)

    def program(h, lp):
        x = rms_norm(h, lp["ln1"], cfg.rms_eps)
        return h + cca.cca_sublayer(x, lp, cfg, positions=llama.packed_positions(seg, S),
                                    segment_ids=seg)

    def reference(h, lp):
        return jnp.stack([zaya_decoder.cca(h[b], lp, ZAYA.shape_of(cfg), seg[b]) for b in range(B)])

    with jax.default_matmul_precision("highest"):
        if what == "forward":
            np.testing.assert_allclose(np.asarray(jax.jit(program)(h, lp)),
                                       np.asarray(jax.jit(reference)(h, lp)), rtol=2e-5, atol=2e-5)
            return
        got = jax.jit(jax.grad(lambda h, lp: (program(h, lp) * ct).sum(), argnums=(0, 1)))(h, lp)
        want = jax.jit(jax.grad(lambda h, lp: (reference(h, lp) * ct).sum(), argnums=(0, 1)))(h, lp)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        worst = float(jnp.abs(g - w).max()) / float(jnp.abs(w).max())
        assert worst < 2e-4, (jax.tree_util.keystr(path), worst)


# -- the share adds up ---------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_two_shares_of_the_experts_add_up_to_the_uncut_layer(dtype):
    """Experts 0-1 and 2-3 as two chips' shares of one layer: attention
    and the router are computed alike on both (counted once); the
    expert-layer outputs of the two shares sum to the uncut layer's, and
    so do the gradients of the input; each share's counts are the uncut
    layer's, and what one share computes the other counts as elsewhere."""
    whole = dataclasses.replace(FP32, dtype=dtype)
    lp = layer_of(seeded_params(ZAYA, whole), 0)
    x = jax.random.normal(jax.random.key(5), (B, S, whole.d_model), jnp.float32).astype(dtype)
    state = 0.1 * jax.random.normal(jax.random.key(6), (B, S, whole.router_hidden))

    def share(first, n):
        cfg = dataclasses.replace(whole, experts_held=n, first_expert_held=first)
        held = {**lp, **{k: lp[k][first:first + n] for k in ("w_gate", "w_up", "w_down")}}
        return run(cfg, held)

    @functools.partial(jax.jit, static_argnames="cfg")
    def run(cfg, lp):
        out, vjp, (stats, r) = jax.vjp(
            lambda x: (lambda o, s, r: (o, (s, r)))(*moe.moe_ffn(x, lp, cfg, state)),
            x, has_aux=True)
        return out, vjp(jnp.ones_like(out))[0], stats, r

    full, full_dx, full_stats, full_r = run(whole, lp)
    a, b = share(0, 2), share(2, 2)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(rtol=0.02, atol=0.02)
    np.testing.assert_allclose(np.asarray(a[0] + b[0], np.float32),
                               np.asarray(full, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(a[1] + b[1], np.float32),
                               np.asarray(full_dx, np.float32), **tol)
    for _, _, stats, r in (a, b):
        assert stats["tokens_per_expert"].tolist() == full_stats["tokens_per_expert"].tolist()
        assert np.array_equal(np.asarray(r), np.asarray(full_r))
    counts = full_stats["tokens_per_expert"]
    assert int(a[2]["pairs_elsewhere"]) == int(counts[2:].sum())
    assert int(b[2]["pairs_elsewhere"]) == int(counts[:2].sum())
    assert int(a[2]["dropped_pairs"]) == int(b[2]["dropped_pairs"]) == 0


def test_a_share_must_lie_inside_the_experts():
    cfg = dataclasses.replace(FP32, experts_held=3, first_expert_held=2)
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="held of 4"):
        moe.moe_ffn(x, layer_of(seeded_params(ZAYA, FP32), 0), cfg)


def test_a_share_of_a_linear_router_model_adds_up_too():
    """The share is the expert layer's, not ZAYA1's: OLMoE-kind routing
    (linear router, top-2) over two shares."""
    whole = dataclasses.replace(moe.MOE_TINY, dtype=jnp.float32, norm_topk_prob=False)
    lp = layer_of(llama.init_params(whole, jax.random.key(0)), 0)
    x = jax.random.normal(jax.random.key(7), (B, S, whole.d_model), jnp.float32)
    parts = []
    for first in (0, 2):
        cfg = dataclasses.replace(whole, experts_held=2, first_expert_held=first)
        held = {**lp, **{k: lp[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")}}
        parts.append(moe.moe_ffn(x, held, cfg)[0])
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(moe.moe_ffn(x, lp, whole)[0]), rtol=1e-5, atol=1e-5)


# -- the grouped matmul whose trailing rows no tile visits -----------------------


@pytest.mark.parametrize("sizes", [[100, 0, 156, 37], [0, 0, 0, 0], [128, 128, 128, 128],
                                   [511, 1, 0, 0]])
def test_grouped_matmul_leaves_the_rows_past_the_last_group_zero(sizes):
    """Interpret mode, P = 1024 rows of which sum(sizes) have a group:
    value and both gradients are ragged_dot's, the rows after the last
    group are zero in the output and in the input gradient though the
    cotangent there is not, and the buffer they were never written to is
    not what comes out."""
    P, K, N = 1024, 128, 256
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(P, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, K, N)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(P, N)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    total = sum(sizes)

    def kernel(a, b):
        return gm.grouped_matmul_pallas(a, b, gs, interpret=True, tail=True)

    def plain(a, b):
        return jax.lax.ragged_dot(a, b, gs, precision=jax.lax.Precision.HIGHEST)

    out, vjp = jax.vjp(kernel, lhs, rhs)
    want, want_vjp = jax.vjp(plain, lhs, rhs)
    d_lhs, d_rhs = vjp(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-3)
    for got, ref in zip((d_lhs, d_rhs), want_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-3)
    assert not np.asarray(out[total:]).any() and not np.asarray(d_lhs[total:]).any()
    assert total == 0 or np.asarray(out[:total]).any()


def test_grouped_matmul_tiles_for_the_held_experts_of_zaya1():
    """From the shapes, as PR 27's: 8 groups over [tokens, 2048] x
    [2048, 2048] keep a group's whole weight matrix in VMEM while its
    rows stream past (it fills the budget to the byte: 40 MiB), forward
    and input gradient; the weight gradient accumulates half of it at a
    time; OLMoE's are what they were."""
    for tokens in (12288, 16384):
        fwd = gm.pick_tiles(tokens, 2048, 2048, jnp.bfloat16)
        assert fwd == gm.Tiles(512, 2048, 2048)
        wgrad = gm.pick_tiles(tokens, 2048, 2048, jnp.bfloat16, wgrad=True)
        assert wgrad.tm == 512 and wgrad.tk * wgrad.tn == 2048 * 1024
        for t, w in ((fwd, False), (wgrad, True)):
            assert gm._vmem_bytes(t, 2, wgrad=w) <= gm._VMEM_BUDGET
    assert gm.pick_tiles(196608, 2048, 1024, jnp.bfloat16) == gm.Tiles(512, 2048, 1024)
    assert gm.pick_tiles(196608, 1024, 2048, jnp.bfloat16) == gm.Tiles(512, 1024, 2048)
