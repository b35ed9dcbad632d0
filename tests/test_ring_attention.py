"""Context parallelism: ring + Ulysses attention vs the XLA reference.

Runs on the 8-virtual-CPU-device mesh (conftest.py), the analog of the
reference's fake multi-node clusters (SURVEY.md §4.3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.parallel.context import parallel_context
from ray_tpu.parallel.mesh import MeshSpec, make_mesh


def _qkv(key, B=2, S=64, H=8, K=4, D=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, K, D), dtype)
    v = jax.random.normal(kv, (B, S, K, D), dtype)
    return q, k, v


@pytest.fixture(scope="module")
def sp_mesh(cpu_devices):
    return make_mesh(MeshSpec(dp=2, sp=4), devices=cpu_devices)


def test_ring_matches_xla_causal(sp_mesh):
    q, k, v = _qkv(jax.random.key(0))
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(
        lambda q, k, v: ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_noncausal(sp_mesh):
    q, k, v = _qkv(jax.random.key(1), S=32)
    ref = xla_attention(q, k, v, causal=False)
    out = ring_attention(q, k, v, mesh=sp_mesh, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_segment_ids(sp_mesh):
    B, S = 2, 64
    q, k, v = _qkv(jax.random.key(2), B=B, S=S)
    # two packed documents per row, different split points
    seg = jnp.stack(
        [
            jnp.where(jnp.arange(S) < 24, 0, 1),
            jnp.where(jnp.arange(S) < 40, 0, 1),
        ]
    ).astype(jnp.int32)
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    out = ring_attention(q, k, v, mesh=sp_mesh, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_gradients_match(sp_mesh):
    q, k, v = _qkv(jax.random.key(3), S=32)

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, mesh=sp_mesh, causal=True) ** 2).sum()

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)   # bare: an operation a compile
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_ulysses_matches_xla(sp_mesh):
    q, k, v = _qkv(jax.random.key(4), H=8, K=4)
    ref = xla_attention(q, k, v, causal=True)
    out = jax.jit(
        lambda q, k, v: ulysses_attention(q, k, v, mesh=sp_mesh, causal=True)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_segment_ids(sp_mesh):
    B, S = 2, 32
    q, k, v = _qkv(jax.random.key(5), B=B, S=S)
    seg = jnp.stack(
        [jnp.where(jnp.arange(S) < 12, 0, 1), jnp.where(jnp.arange(S) < 20, 0, 1)]
    ).astype(jnp.int32)
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    out = ulysses_attention(q, k, v, mesh=sp_mesh, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_sp1_shortcircuit(cpu_devices):
    mesh = make_mesh(MeshSpec(dp=8), devices=cpu_devices)
    q, k, v = _qkv(jax.random.key(6), S=16)
    ref = xla_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_llama_forward_ring_matches_xla(sp_mesh):
    """End-to-end: llama with attention_impl='ring' under parallel_context."""
    import dataclasses

    from ray_tpu.models import llama

    cfg = llama.LLAMA_TINY
    cfg_ring = dataclasses.replace(cfg, attention_impl="ring")
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size, jnp.int32)

    ref = llama.forward(params, tokens, cfg)
    with parallel_context(sp_mesh):
        out = jax.jit(lambda p, t: llama.forward(p, t, cfg_ring))(params, tokens)
    # bf16 end-to-end: sharded vs unsharded GSPMD tilings round single
    # elements differently across jax versions — 5e-2 covers the observed
    # 1-in-65536 outlier at 3.7e-2 without masking a real mismatch
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=5e-2, rtol=5e-2
    )


def test_train_step_with_ring_attention(sp_mesh):
    """Full sharded train step with the CP axis active (sp=4)."""
    import dataclasses

    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.sharding import default_rules, tree_shardings
    from ray_tpu.train.step import TrainState, init_sharded_params, make_train_step

    cfg = dataclasses.replace(llama.LLAMA_TINY, attention_impl="ring")
    rules = default_rules()
    params = init_sharded_params(
        lambda: llama.init_params(cfg, jax.random.key(0)),
        llama.logical_axes(cfg),
        sp_mesh,
        rules,
    )
    opt = optax.adamw(1e-3)
    state = TrainState.create(params, opt)
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh=sp_mesh, rules=rules
    )
    toks = jax.random.randint(jax.random.key(1), (4, 65), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    from ray_tpu.parallel.sharding import tree_shardings as ts

    batch = jax.device_put(
        batch, ts(sp_mesh, rules, jax.tree.map(lambda x: ("batch", "seq"), batch))
    )
    state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"])
