"""GLM-4.7-Flash through the one decoder (PR 34), at a small size on the
CPU, seeded weights, against the plain reference
(chipbench/reference/glm_lite_decoder.py, imported): the MLA sublayer
alone, sigmoid top-k routing with its bias, renormalisation, scaling and
the shared expert, the shares that add up, the preset's counts, and the
kernels at the new shapes (flash at heads of 256, grouped matmul at K 2048
/ N 1536). (The whole train path in loss and gradients, MTP's shift, remat
and bf16: tests/test_contract_glm_lite.py; `config_from_hf` and the
engine's refusal: tests/test_model_contract.py.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import glm_lite_decoder
from model_cases import GLM_LITE, seeded_params, worst_leaf
from ray_tpu.models import llama, mla, moe
from ray_tpu.models.registry import get_model_config
from ray_tpu.nn.layers import rms_norm
from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash import flash_attention

FP32, B, S = GLM_LITE.fp32, GLM_LITE.batch, GLM_LITE.seq


def layer_of(params, i):
    layers = {**params["layers"], "router_bias": params["layers"]["router_bias"][:-1]}
    return jax.tree.map(lambda x: x[i], layers)


# -- the sublayers against the reference ---------------------------------------


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_mla_sublayer_is_the_references(what):
    """The attention half of a block alone: x -> x + MLA(RMSNorm(x)), the
    one rotary key shared by the heads, forward and the gradients of the
    input and of every weight."""
    lp = layer_of(seeded_params(GLM_LITE, FP32), 1)
    x = jax.random.normal(jax.random.key(3), (B, S, FP32.d_model), jnp.float32)
    shape = GLM_LITE.shape_of(FP32)

    def program(x, lp):
        normed = rms_norm(x, lp["ln1"], FP32.rms_eps)
        return x + mla.mla_sublayer(normed, lp, FP32, positions=jnp.arange(S), segment_ids=None)

    def reference(x, lp):
        return jnp.stack([glm_lite_decoder.mla(x[b], lp, shape) for b in range(B)])

    with jax.default_matmul_precision("highest"):
        if what == "forward":
            np.testing.assert_allclose(np.asarray(jax.jit(program)(x, lp)),
                                       np.asarray(jax.jit(reference)(x, lp)), rtol=2e-5, atol=2e-5)
            return
        probe = jax.random.normal(jax.random.key(4), (B, S, FP32.d_model))
        got = jax.jit(jax.grad(lambda x, lp: (program(x, lp) * probe).sum(), argnums=(0, 1)))(x, lp)
        want = jax.jit(jax.grad(lambda x, lp: (reference(x, lp) * probe).sum(), argnums=(0, 1)))(x, lp)
    used = [k for k in mla.attention_axes()] + ["ln1"]
    worst = worst_leaf({"x": got[0], **{k: got[1][k] for k in used}},
                       {"x": want[0], **{k: want[1][k] for k in used}})
    assert max(worst.values()) < 1e-4, worst


def test_the_positions_reach_the_sublayer_through_the_rotary_channels_alone():
    """Rotary is on q_rot and k_rot alone: with the queries' rotary
    channels zero (the scores then read no k_rot) the sublayer does not
    see the positions at all."""
    lp = layer_of(seeded_params(GLM_LITE, FP32), 0)
    x = jax.random.normal(jax.random.key(3), (B, S, FP32.d_model), jnp.float32)
    run = lambda lp, pos: mla.mla_sublayer(x, lp, FP32, positions=pos, segment_ids=None)  # noqa: E731
    assert not np.allclose(run(lp, jnp.arange(S)), run(lp, 3 * jnp.arange(S) + 5), atol=1e-4)
    dn, dr, H = FP32.qk_nope_head_dim, FP32.qk_rope_head_dim, FP32.n_heads
    wq = lp["wq_b"].reshape(-1, H, dn + dr).at[..., dn:].set(0.0).reshape(lp["wq_b"].shape)
    blind = {**lp, "wq_b": wq}
    np.testing.assert_allclose(run(blind, jnp.arange(S)), run(blind, 3 * jnp.arange(S) + 5),
                               rtol=1e-5, atol=1e-6)


def test_sigmoid_routing_bias_renormalisation_scaling_and_the_shared_expert():
    """`moe_ffn` under a sigmoid router, by a per-token loop in numpy: the
    bias chooses and is no part of the weight; the chosen scores are
    renormalised over the chosen and scaled by 1.8; the shared expert is
    added for every token; counts sum to top_k x tokens."""
    lp = layer_of(seeded_params(GLM_LITE, FP32), 0)
    x = jax.random.normal(jax.random.key(5), (B, S, FP32.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, stats, _ = moe.moe_ffn(x, lp, FP32)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    xt = f(x).reshape(B * S, -1)
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    scores = 1.0 / (1.0 + np.exp(-(xt @ f(lp["router"]))))
    want, counts = np.zeros_like(xt), np.zeros(FP32.n_experts, np.int64)
    for t in range(B * S):
        chosen = np.argsort(-(scores[t] + f(lp["router_bias"])))[:FP32.top_k]
        w = FP32.routed_scaling * scores[t, chosen] / scores[t, chosen].sum()
        for e, we in zip(chosen, w):
            counts[e] += 1
            want[t] += we * ((silu(xt[t] @ f(lp["w_gate"][e])) * (xt[t] @ f(lp["w_up"][e])))
                             @ f(lp["w_down"][e]))
        want[t] += ((silu(xt[t] @ f(lp["shared_gate"])) * (xt[t] @ f(lp["shared_up"])))
                    @ f(lp["shared_down"]))
    np.testing.assert_allclose(f(out).reshape(B * S, -1), want, rtol=2e-4, atol=2e-5)
    assert stats["tokens_per_expert"].tolist() == counts.tolist()
    assert int(stats["tokens_per_expert"].sum()) == FP32.top_k * B * S
    assert int(stats["dropped_pairs"]) == 0 and float(stats["z_loss"]) == 0.0
    # the bias alone moves the choice: with a large bias on one expert every token takes it,
    # at a weight that is still its own score's share
    tilted = {**lp, "router_bias": lp["router_bias"].at[3].set(10.0)}
    _, tilted_stats, _ = moe.moe_ffn(x, tilted, FP32)
    assert int(tilted_stats["tokens_per_expert"][3]) == B * S
    # and takes no gradient
    g = jax.grad(lambda b: moe.moe_ffn(x, {**lp, "router_bias": b}, FP32)[0].sum())(
        lp["router_bias"])
    assert float(jnp.abs(g).max()) == 0.0


def test_old_router_kinds_keep_their_parameters_and_their_weights():
    """A softmax router has no selection bias of this kind and no shared
    expert; its top-k weights are not scaled."""
    params = llama.init_params(moe.MOE_TINY, jax.random.key(0))
    assert "router_bias" not in params["layers"] and "shared_gate" not in params["layers"]
    assert set(moe.expert_axes(moe.MOE_TINY)) == {"router", "w_gate", "w_up", "w_down"}
    assert set(moe.expert_axes(FP32)) == {"router", "router_bias", "w_gate", "w_up", "w_down",
                                          "shared_gate", "shared_up", "shared_down"}


# -- a model that is one stack -------------------------------------------------------


def test_a_model_with_neither_dense_layer_nor_mtp_block_is_one_stack():
    cfg = dataclasses.replace(FP32, first_dense_layers=0, mtp_layers=0, n_layers=2)
    params = llama.init_params(cfg, jax.random.key(0))
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"}
    assert params["layers"]["router_bias"].shape == (2, cfg.n_experts)
    out = llama.loss_and_weight_fn(params, GLM_LITE.batch_of(cfg), cfg)
    assert "loss_mtp" not in out[2] and np.isfinite(float(out[0]))
    with pytest.raises(ValueError, match="0 or 1"):
        llama.init_params(dataclasses.replace(FP32, mtp_layers=2), jax.random.key(0))


# -- the share adds up ---------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shares_of_the_experts_add_up_to_the_uncut_layer(dtype):
    """Four shares of 2 of the 8 experts (the cell's eight shares of 8 of
    64, small): the router and the shared expert are computed alike on
    every chip and counted ONCE; the routed outputs of the shares, so
    counted, sum to the uncut layer's, and so do the gradients of the
    input; each share's counts are the uncut layer's, and what one share
    computes the others count as elsewhere."""
    whole = dataclasses.replace(FP32, dtype=dtype)
    lp = layer_of(seeded_params(GLM_LITE, whole), 0)
    x = jax.random.normal(jax.random.key(5), (B, S, whole.d_model), jnp.float32).astype(dtype)
    no_shared = {k: (jnp.zeros_like(v) if k == "shared_down" else v) for k, v in lp.items()}

    def run(cfg, lp):
        out, vjp, stats = jax.vjp(lambda x: moe.moe_ffn(x, lp, cfg)[:2], x, has_aux=True)
        return out, vjp(jnp.ones_like(out))[0], stats

    def share(first, n, lp):
        cfg = dataclasses.replace(whole, experts_held=n, first_expert_held=first)
        return run(cfg, {**lp, **{k: lp[k][first:first + n] for k in ("w_gate", "w_up", "w_down")}})

    full = run(whole, lp)
    shared_alone = run(whole, {**lp, "w_down": jnp.zeros_like(lp["w_down"])})
    routed = [share(first, 2, no_shared) for first in (0, 2, 4, 6)]
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(rtol=0.03, atol=0.03)
    for i in (0, 1):  # the output, and the gradient of the input
        total = sum(np.asarray(r[i], np.float32) for r in routed) + np.asarray(
            shared_alone[i], np.float32)
        if i == 1:  # the router's own path to x is in every term: counted once
            router_only = np.asarray(run(whole, {**no_shared, "w_down": jnp.zeros_like(
                lp["w_down"])})[1], np.float32)
            total = total - 4 * router_only
        np.testing.assert_allclose(total, np.asarray(full[i], np.float32), **tol)
    counts = full[2]["tokens_per_expert"]
    assert int(counts.sum()) == whole.top_k * B * S
    for first, (_, _, stats) in zip((0, 2, 4, 6), routed):
        assert stats["tokens_per_expert"].tolist() == counts.tolist()
        assert int(stats["pairs_elsewhere"]) == int(counts.sum() - counts[first:first + 2].sum())
        assert int(stats["dropped_pairs"]) == 0
    # a share with its shared expert is the share's routed part + the shared expert, whole
    with_shared = share(2, 2, lp)
    np.testing.assert_allclose(
        np.asarray(with_shared[0], np.float32),
        np.asarray(routed[1][0], np.float32) + np.asarray(shared_alone[0], np.float32), **tol)


# -- the registry ------------------------------------------------------------------


def test_counts_of_parameters_and_operations_are_30b_a3bs_and_the_trees():
    cfg = get_model_config("glm-4.7-flash")
    assert isinstance(cfg, mla.GlmLiteConfig)
    # 30B-A3B: every expert somewhere; 6 x the active parameters is most of a token's operations
    assert 30.0e9 < dataclasses.replace(cfg).num_params() < 31.2e9
    active = cfg.flops_per_token(1) / 2
    assert 3.0e9 < active < 4.0e9
    # the preset's tree has the sizes num_params counts, at a small depth
    small = dataclasses.replace(mla.GLM_LITE_TINY, experts_held=4)
    n = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: llama.init_params(small, jax.random.key(0)))))
    assert n == small.num_params()


# -- the kernels at the new shapes -------------------------------------------------------


@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_flash_at_heads_of_256_is_xla_attention(what):
    """20 heads of 256, none shared: the flash kernels (interpret mode)
    against `xla_attention`, forward and the three gradients, at a
    sequence that takes two q blocks."""
    b, s, h, d = 1, 1024, 2, 256
    q, k, v = (jax.random.normal(jax.random.key(i), (b, s, h, d), jnp.float32) * 0.5
               for i in (1, 2, 3))
    probe = jax.random.normal(jax.random.key(4), (b, s, h, d), jnp.float32)
    if what == "forward":
        np.testing.assert_allclose(np.asarray(flash_attention(q, k, v, causal=True)),
                                   np.asarray(xla_attention(q, k, v, causal=True)),
                                   rtol=2e-4, atol=2e-4)
        return
    got = jax.grad(lambda *a: (flash_attention(*a, causal=True) * probe).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (xla_attention(*a, causal=True) * probe).sum(), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-3)


def test_grouped_matmul_at_the_held_experts_shapes_with_a_tail():
    """K 2048 / N 1536 (and the transpose), 8 groups, rows past the last
    group: the tile rule accepts the shapes with the whole contraction
    in one block, and the kernels (interpret mode, a smaller K and N of
    the same ratio) leave the tail zero in the output and both gradients."""
    for K, N in ((2048, 1536), (1536, 2048)):
        for wgrad in (False, True):
            t = gm.pick_tiles(32768, K, N, jnp.bfloat16, wgrad=wgrad)
            assert t is not None and t.tm == 512
            assert gm._vmem_bytes(t, 2, wgrad=wgrad) <= gm._VMEM_BUDGET
        assert gm.pick_tiles(32768, K, N, jnp.bfloat16).tk == K  # a group's weights stay in VMEM
    P, K, N, E = 1024, 256, 384, 8
    sizes = jnp.asarray([100, 0, 156, 37, 64, 1, 90, 40], jnp.int32)  # 488 rows held of 1024
    lhs = jax.random.normal(jax.random.key(0), (P, K), jnp.float32)
    rhs = jax.random.normal(jax.random.key(1), (E, K, N), jnp.float32) / 16

    def kernel(lhs, rhs):
        return gm.grouped_matmul_pallas(lhs, rhs, sizes, interpret=True, tail=True)

    def plain(lhs, rhs):
        return jax.lax.ragged_dot(lhs, rhs, sizes, precision=jax.lax.Precision.HIGHEST)

    held = int(sizes.sum())
    got, want = kernel(lhs, rhs), plain(lhs, rhs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)
    assert float(jnp.abs(got[held:]).max()) == 0.0
    probe = jax.random.normal(jax.random.key(2), (P, N), jnp.float32)
    g_got = jax.grad(lambda a, b: (kernel(a, b) * probe).sum(), (0, 1))(lhs, rhs)
    g_want = jax.grad(lambda a, b: (plain(a, b) * probe).sum(), (0, 1))(lhs, rhs)
    for g, w in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-3)
    assert float(jnp.abs(g_got[0][held:]).max()) == 0.0
