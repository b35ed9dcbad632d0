"""Flash-attention kernel vs the XLA composite (interpreter mode on CPU).

Mirrors the reference's kernel-parity strategy (vLLM kernels tested
against torch reference impls); here the Pallas kernels run under the
interpreter so CPU CI exercises the real code path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash import flash_attention


def make_qkv(key, B, Sq, Sk, H, KVH, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, H, D), dtype)
    k = jax.random.normal(kk, (B, Sk, KVH, D), dtype)
    v = jax.random.normal(kv, (B, Sk, KVH, D), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "B,S,H,KVH,D,causal",
    [
        (2, 64, 4, 4, 32, True),     # MHA causal
        (2, 64, 4, 2, 32, True),     # GQA
        (1, 128, 8, 2, 64, True),    # deeper GQA, two q blocks at bq=64
        (2, 64, 4, 2, 32, False),    # bidirectional
        (1, 100, 4, 2, 32, True),    # non-divisible seq -> padding path
    ],
)
def test_forward_matches_xla(B, S, H, KVH, D, causal):
    q, k, v = make_qkv(jax.random.key(0), B, S, S, H, KVH, D)
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_bf16_tolerance():
    q, k, v = make_qkv(jax.random.key(1), 2, 128, 128, 4, 2, 64, jnp.bfloat16)
    ref = xla_attention(q, k, v, causal=True).astype(jnp.float32)
    out = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


def test_segment_ids_packing():
    B, S, H, KVH, D = 2, 64, 4, 2, 32
    q, k, v = make_qkv(jax.random.key(2), B, S, S, H, KVH, D)
    seg = jnp.concatenate(
        [jnp.zeros((B, S // 2), jnp.int32), jnp.ones((B, S - S // 2), jnp.int32)],
        axis=1,
    )
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    out = flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=32, block_k=32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_q_offset_decode_window():
    """Short q attending into a longer kv prefix (chunked prefill shape)."""
    B, H, KVH, D = 1, 4, 2, 32
    Sq, Sk, off = 16, 64, 48
    q, k, v = make_qkv(jax.random.key(3), B, Sq, Sk, H, KVH, D)
    ref = xla_attention(q, k, v, causal=True, q_offset=off)
    out = flash_attention(q, k, v, causal=True, q_offset=off, block_q=16, block_k=16)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("KVH", [4, 2])
def test_grads_match_xla(KVH):
    B, S, H, D = 2, 64, 4, 32
    q, k, v = make_qkv(jax.random.key(4), B, S, S, H, KVH, D)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=32, block_k=32) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_grads_with_segments_and_padding():
    B, S, H, KVH, D = 1, 100, 4, 2, 32  # non-divisible: padded blocks
    q, k, v = make_qkv(jax.random.key(5), B, S, S, H, KVH, D)
    seg = (jnp.arange(S)[None, :] >= 40).astype(jnp.int32)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, segment_ids=seg) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, segment_ids=seg, block_q=32, block_k=32
            ) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_llama_forward_with_flash():
    """The model's attention_impl='flash' config path end to end."""
    import dataclasses

    from ray_tpu.models import llama

    cfg = dataclasses.replace(
        llama.LLAMA_TINY, attention_impl="flash", dtype=jnp.float32
    )
    cfg_ref = dataclasses.replace(cfg, attention_impl="xla")
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    out = llama.forward(params, tokens, cfg)
    ref = llama.forward(params, tokens, cfg_ref)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_flash_under_jit_and_grad_jit():
    q, k, v = make_qkv(jax.random.key(6), 1, 64, 64, 4, 2, 32)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True)

    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(f(q, k, v), ref, atol=2e-5, rtol=2e-5)

    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) ** 2)))
    assert np.isfinite(np.asarray(g(q, k, v))).all()


@pytest.mark.parametrize("nk_blocks", [1, 2])
def test_fold_heads_parity(nk_blocks):
    """Folded (F=G) and unfolded (F=1) kernels must agree bit-for-bit in
    fwd and grads, on both the fused (nk=1) and unfused (nk>1) backward
    paths, with GQA group 4."""
    B, S, H, KVH, D = 2, 128, 8, 2, 32
    bk = 128 // nk_blocks
    q, k, v = make_qkv(jax.random.key(7), B, S, S, H, KVH, D)

    def loss(fold):
        def f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, block_q=32, block_k=bk,
                                fold_heads=fold) ** 2)
        return f

    o1 = flash_attention(q, k, v, causal=True, block_q=32, block_k=bk,
                         fold_heads=1)
    o4 = flash_attention(q, k, v, causal=True, block_q=32, block_k=bk,
                         fold_heads=4)
    np.testing.assert_allclose(o4, o1, atol=1e-6, rtol=1e-6)
    # grads: folding reorders the dk/dv reduction (one wide contraction
    # vs sequential adds) — identical math, f32 rounding differs
    g1 = jax.grad(loss(1), argnums=(0, 1, 2))(q, k, v)
    g4 = jax.grad(loss(4), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g4, g1, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} fold mismatch")


@pytest.mark.parametrize("with_segments", [False, True])
def test_attention_flash_under_mesh_matches_xla(cpu_devices, with_segments):
    """Under a multi-device mesh `attention(impl="flash")` runs one
    kernel per shard inside a shard_map (a Mosaic kernel has no
    partitioning rule): batch over fsdp, heads over tp, values and
    gradients as the unsharded composite."""
    from ray_tpu.ops.attention import attention
    from ray_tpu.parallel.context import parallel_context
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), devices=cpu_devices[:4])
    B, S, H, KVH, D = 4, 64, 4, 2, 32
    q, k, v = make_qkv(jax.random.key(7), B, S, S, H, KVH, D)
    seg = (jnp.arange(S)[None, :] >= S // 2).astype(jnp.int32).repeat(B, 0)
    seg = seg if with_segments else None

    def loss(impl, q, k, v):
        with parallel_context(mesh):
            o = attention(q, k, v, causal=True, segment_ids=seg, impl=impl)
        return (o * o).sum(), o

    (_, ref), gref = jax.value_and_grad(
        lambda *a: loss("xla", *a), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, out), gout = jax.jit(jax.value_and_grad(
        lambda *a: loss("flash", *a), argnums=(0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for a, b in zip(gout, gref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("mesh_spec", [None, dict(fsdp=2, tp=2)], ids=["no_mesh", "fsdp2_tp2"])
def test_head_major_entry_is_the_other_entry_without_its_transposes(cpu_devices, mesh_spec):
    """`attention_head_major` takes and gives [B, H, S, D], the kernels'
    own layout (models/cca.py holds its heads so); `attention` is its
    transposes around the same kernels: outputs and gradients are equal
    to the bit, packed documents and a ragged sequence included, alone
    and with batch and heads sharded over a mesh."""
    import contextlib

    from ray_tpu.ops.attention import attention, attention_head_major
    from ray_tpu.parallel.context import parallel_context
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    B, S, H, KVH, D = 4, 100, 4, 2, 32
    q, k, v = make_qkv(jax.random.key(11), B, S, S, H, KVH, D)
    seg = (jnp.arange(S)[None, :] >= 37).astype(jnp.int32).repeat(B, 0)
    ct = jax.random.normal(jax.random.key(12), (B, S, H, D))
    mesh = None if mesh_spec is None else make_mesh(MeshSpec(**mesh_spec), devices=cpu_devices[:4])

    def context():
        return contextlib.nullcontext() if mesh is None else parallel_context(mesh)

    def rows_major(q, k, v):
        with context():
            o = attention(q, k, v, causal=True, segment_ids=seg, impl="flash")
        return (o * ct).sum(), o

    def head_major(q, k, v):
        with context():
            o = attention_head_major(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)),
                                     causal=True, segment_ids=seg, impl="flash")
        o = jnp.swapaxes(o, 1, 2)
        return (o * ct).sum(), o

    (_, want), g_want = jax.jit(jax.value_and_grad(rows_major, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, got), g_got = jax.jit(jax.value_and_grad(head_major, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert np.array_equal(np.asarray(got), np.asarray(want)) and np.asarray(want).any()
    for a, b in zip(g_got, g_want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # every other impl goes through `attention` itself
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(attention_head_major(
            *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), segment_ids=seg, impl="xla"), 1, 2)),
        np.asarray(want), atol=2e-5, rtol=2e-5)
