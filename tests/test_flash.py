"""Flash-attention kernel vs the XLA composite (interpreter mode on CPU).

Mirrors the reference's kernel-parity strategy (vLLM kernels tested
against torch reference impls); here the Pallas kernels run under the
interpreter so CPU CI exercises the real code path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as _attention
from ray_tpu.ops import flash as _flash

# One program a shape and set of options for the composite and for the kernels' wrapper:
# taken bare, every operation of the composite (and of its gradient) is compiled alone for
# each case's shapes, which was most of this file's seconds. The options are static, as the
# models pass them; `test_flash_under_jit_and_grad_jit` is the jit of a caller's own.
_MASK_OPTIONS = ("causal", "q_offset", "softmax_scale", "window")
xla_attention = jax.jit(_attention.xla_attention, static_argnames=_MASK_OPTIONS)
flash_attention = jax.jit(_flash.flash_attention, static_argnames=_MASK_OPTIONS + (
    "block_q", "block_k", "interpret", "fold_heads", "return_lse"))


def make_qkv(key, B, Sq, Sk, H, KVH, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, H, D), dtype)
    k = jax.random.normal(kk, (B, Sk, KVH, D), dtype)
    v = jax.random.normal(kv, (B, Sk, KVH, D), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "B,S,H,KVH,D,causal,bq,bk",
    [
        (2, 64, 4, 4, 32, True, 64, 64),     # MHA causal
        (2, 64, 4, 2, 32, True, 64, 64),     # GQA
        (1, 128, 8, 2, 64, True, 64, 64),    # deeper GQA, two q blocks at bq=64
        (2, 64, 4, 2, 32, False, 64, 64),    # bidirectional
        (1, 100, 4, 2, 32, True, 64, 64),    # non-divisible seq -> padding path
        # the kv block walked in 512-wide sub-tiles, in one program some below the
        # diagonal, some crossed by it, some skipped: heads of 64, 128 (two heads folded), 256
        (1, 1536, 2, 1, 64, True, 512, None),
        (1, 1536, 4, 2, 128, True, 512, None),
        (1, 1536, 2, 2, 256, True, 512, None),
        (1, 1024, 2, 1, 64, True, 256, None),   # two q blocks a sub-tile: the diagonal crosses it twice
        # a padded edge inside a sub-tile (keys 1300..1535) and a sub-tile that is all
        # padding, over two kv blocks; without a diagonal the edge alone is crossed
        (1, 1300, 2, 1, 64, True, 512, 1024),
        (1, 1300, 2, 1, 64, False, 512, 1024),
        (1, 1024, 2, 2, 64, False, 512, None),  # no mask term at all, a static walk
    ],
)
def test_forward_matches_xla(B, S, H, KVH, D, causal, bq, bk):
    q, k, v = make_qkv(jax.random.key(0), B, S, S, H, KVH, D)
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,KVH,D", [
    (2, 128, 4, 2, 64),
    (1, 1536, 4, 1, 128),  # sub-tiles below, on and above the diagonal, four heads folded
    (1, 1024, 2, 2, 256),
])
def test_forward_bf16_tolerance(B, S, H, KVH, D):
    q, k, v = make_qkv(jax.random.key(1), B, S, S, H, KVH, D, jnp.bfloat16)
    ref = xla_attention(q, k, v, causal=True).astype(jnp.float32)
    out = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("S,cut,bq,bk", [
    (64, 32, 32, 32),
    # the boundary falls inside a sub-tile wholly below the diagonal (keys 0..511
    # against rows 512..1535)
    (1536, 200, 512, None),
    (1536, 700, 512, None),
    # PR 69: four sub-tiles and eight q blocks in ONE kv block, where the walk of the second
    # document's q blocks starts at the sub-tile of its first key (tests/test_flash_documents.py
    # has the walk's own cases): the boundary inside a sub-tile and on a sub-tile's edge
    (2048, 700, 256, None),
    (2048, 1024, 256, None),
])
def test_segment_ids_packing(S, cut, bq, bk):
    B, H, KVH, D = 2, 4, 2, 32
    q, k, v = make_qkv(jax.random.key(2), B, S, S, H, KVH, D)
    seg = jnp.concatenate(
        [jnp.zeros((B, cut), jnp.int32), jnp.ones((B, S - cut), jnp.int32)],
        axis=1,
    )
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    out = flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=bq, block_k=bk)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Sq,Sk,off,bq,bk", [
    (16, 64, 48, 16, 16),
    # rows 1024..1535 against 1536 keys in three sub-tiles: two below the diagonal, one on it
    (512, 1536, 1024, 256, None),
    # the diagonal off the sub-tiles' grid: rows 700..1211, two sub-tiles crossed
    (512, 1536, 700, 512, None),
])
def test_q_offset_decode_window(Sq, Sk, off, bq, bk):
    """Short q attending into a longer kv prefix (chunked prefill shape)."""
    B, H, KVH, D = 1, 4, 2, 32
    q, k, v = make_qkv(jax.random.key(3), B, Sq, Sk, H, KVH, D)
    ref = xla_attention(q, k, v, causal=True, q_offset=off)
    out = flash_attention(q, k, v, causal=True, q_offset=off, block_q=bq, block_k=bk)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g_ref = jax.jit(jax.grad(lambda *a: jnp.sum(xla_attention(*a, causal=True, q_offset=off) ** 2),
                             argnums=(0, 1, 2)))(q, k, v)
    g_out = jax.jit(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, q_offset=off, block_q=bq, block_k=bk) ** 2), argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("S,H,KVH,D,causal,bq,bk", [
    (64, 4, 4, 32, True, 32, 32),
    (64, 4, 2, 32, True, 32, 32),
    # the fused backward over 512-wide sub-tiles below, on and above the diagonal, at
    # heads of 64, 128 (two heads folded) and 256
    (1536, 2, 1, 64, True, 512, None),
    (1536, 4, 2, 128, True, 512, None),
    (1024, 2, 2, 256, True, 512, None),
    # two kv blocks (dq and dk/dv apart), a padded edge inside a sub-tile and a
    # sub-tile of padding alone, rows of padding in the last q block
    (1300, 2, 1, 64, True, 512, 1024),
    (1300, 2, 1, 64, False, 512, 1024),
], ids=lambda x: str(x))
def test_grads_match_xla(S, H, KVH, D, causal, bq, bk):
    B = 2 if S < 1024 else 1
    q, k, v = make_qkv(jax.random.key(4), B, S, S, H, KVH, D)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk) ** 2
        )

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_out = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name} mismatch"
        )


@pytest.mark.parametrize("S,cut,bq,bk", [
    (100, 40, 32, 32),
    # the boundary inside a sub-tile below the diagonal, the padded edge inside another
    (1400, 200, 512, 1024),
    (1536, 200, 512, None),
    # PR 69: the documents' range over ONE kv block with a padded edge (the last q block's
    # padded rows and the last sub-tile's padded keys are in neither side's range of ids),
    # and the same boundary over four kv blocks, where the walk stays positional
    (2000, 700, 256, None),
    (2000, 768, 256, 512),
])
def test_grads_with_segments_and_padding(S, cut, bq, bk):
    B, H, KVH, D = 1, 4, 2, 32  # non-divisible: padded blocks
    q, k, v = make_qkv(jax.random.key(5), B, S, S, H, KVH, D)
    seg = (jnp.arange(S)[None, :] >= cut).astype(jnp.int32)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True, segment_ids=seg) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, segment_ids=seg, block_q=bq, block_k=bk
            ) ** 2
        )

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_out = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
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


@pytest.mark.parametrize("S,bq,nk_blocks", [(128, 32, 1), (128, 32, 2), (1024, 256, 1)],
                         ids=["1", "2", "sub_tiles"])
def test_fold_heads_parity(S, bq, nk_blocks):
    """Folded (F=G) and unfolded (F=1) kernels must agree bit-for-bit in
    fwd and grads, on both the fused (nk=1) and unfused (nk>1) backward
    paths, with GQA group 4; and where the kv block is walked in
    sub-tiles, whose mask is one [Bq, Tk] array for the four heads."""
    B, H, KVH, D = 2, 8, 2, 32
    bk = S // nk_blocks
    q, k, v = make_qkv(jax.random.key(7), B, S, S, H, KVH, D)

    def loss(fold):
        def f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                fold_heads=fold) ** 2)
        return f

    o1 = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                         fold_heads=1)
    o4 = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                         fold_heads=4)
    np.testing.assert_allclose(o4, o1, atol=1e-6, rtol=1e-6)
    # grads: folding reorders the dk/dv reduction (one wide contraction
    # vs sequential adds) — identical math, f32 rounding differs
    g1 = jax.jit(jax.grad(loss(1), argnums=(0, 1, 2)))(q, k, v)
    g4 = jax.jit(jax.grad(loss(4), argnums=(0, 1, 2)))(q, k, v)
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


@pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 512), (True, 300)])
@pytest.mark.parametrize("Bq,Bk,Tk,q_offset,sq_valid,sk_valid", [
    (512, 4096, 512, 0, 4096, 4096),   # the cells' shape: 36 of 64 sub-tiles run, 15 under a window of 512
    (512, 1536, 512, 0, 1536, 1536),
    (256, 1024, 512, 0, 1024, 1024),   # two q blocks a sub-tile
    (512, 1024, 512, 0, 1300, 1300),   # two kv blocks, a padded edge, a sub-tile of padding
    (256, 1536, 512, 1024, 512, 1536),  # q_offset on the grid
    (512, 1536, 512, 700, 512, 1536),   # and off it
    (32, 32, 32, 0, 100, 100),          # one sub-tile a block
    (16, 16, 16, 48, 16, 64),
])
def test_sub_tiles_skipped_are_those_the_triangle_masks_whole(causal, window, Bq, Bk, Tk, q_offset,
                                                              sq_valid, sk_valid):
    """`_tiles_to_run` (one rule for the forward and the fused backward)
    against the dense triangle: a sub-tile is skipped iff every entry of
    it lies above the diagonal (or, under a window, below the window), and
    the ones that run are a range of the kv block's sub-tiles: a prefix
    without a window."""
    from ray_tpu.ops.flash import _tiles_to_run

    nq, nk, nt = -(-sq_valid // Bq), -(-sk_valid // Bk), Bk // Tk
    i, j = np.arange(nq)[:, None], np.arange(nk)[None, :]
    first, n_run = (np.broadcast_to(np.asarray(x), (nq, nk)) for x in
                    _tiles_to_run(i, j, Bq, Bk, Tk, causal=causal, q_offset=q_offset, window=window))
    if window is None:
        assert not first.any()
    rows, cols = np.arange(nq * Bq)[:, None], np.arange(nk * Bk)[None, :]
    below = (rows + q_offset >= cols) if causal else np.ones((nq * Bq, nk * Bk), bool)
    if window is not None:
        below &= rows + q_offset - cols < window
    for a in range(nq):
        for b in range(nk):
            for t in range(nt):
                tile = (slice(a * Bq, (a + 1) * Bq), slice(b * Bk + t * Tk, b * Bk + (t + 1) * Tk))
                assert (first[a, b] <= t < first[a, b] + n_run[a, b]) == below[tile].any(), (a, b, t)
    if (Bq, Bk, sk_valid) == (512, 4096, 4096):
        assert int(n_run.sum()) == (64 if not causal else 36 if window is None else 15)


def test_rows_with_nothing_to_attend_weigh_nothing():
    """Ring attention's rotating kv blocks: rows whose segment meets no
    key of the block. Where a sub-tile ran they hold a finite mean and
    where none ran zeros, and either way `lse` is about NEG_INF, which
    is what lets the blockwise merge give them no weight; the other rows
    are the composite's. Over sub-tiles below and on the diagonal."""
    from ray_tpu.ops.flash import NEG_INF

    B, S, H, KVH, D = 1, 1024, 2, 1, 32
    q, k, v = make_qkv(jax.random.key(8), B, S, S, H, KVH, D)
    qseg = (jnp.arange(S)[None, :] >= 300).astype(jnp.int32)  # rows 0..299 are segment 0
    kseg = jnp.ones((B, S), jnp.int32)                          # which no key has
    for causal in (True, False):
        o, lse = flash_attention(q, k, v, causal=causal, segment_ids=qseg, kv_segment_ids=kseg,
                                 block_q=256, return_lse=True)
        assert np.isfinite(np.asarray(o)).all()
        assert (np.asarray(lse[:, :300]) < 0.9 * NEG_INF).all()
        assert (np.asarray(lse[:, 300:]) > -1e4).all()
        ref = xla_attention(q[:, 300:], k, v, causal=causal, q_offset=300)
        np.testing.assert_allclose(o[:, 300:], ref, atol=2e-5, rtol=2e-5)
    # keys wholly after the rows (a causal block from the future): no sub-tile runs
    o, lse = flash_attention(q[:, :256], k, v, causal=True, q_offset=-1024, return_lse=True)
    assert not np.asarray(o).any() and (np.asarray(lse) < 0.9 * NEG_INF).all()


# -- the kv block sized by its bytes (PR 43; 4 MiB since PR 56) -------------------


@pytest.mark.parametrize("D,itemsize,Sk,want", [
    (128, 2, 8192, 8192),    # keye-train-8k: one kv block, the backward fused
    (128, 2, 4096, 4096),    # every other cell: what the constant gave
    (256, 2, 8192, 8192),    # MLA's heads at 8192 keys: the bytes of 16,384 keys at heads of 128
    (128, 4, 8192, 8192),    # float32 doubles a block's bytes: 4 MiB, the budget itself
    (64, 2, 8192, 8192),
    (128, 2, 16384, 16384),  # mellum2-train-16k (PR 56): one kv block of 4 MiB, the backward fused
    (64, 2, 16384, 16384),   # VMEM holds a row of 64 in the 128 lanes: as many keys as at 128
    (128, 2, 32768, 4096),   # a sequence over the budget keeps blocks of 4096, the kernels apart
    (256, 2, 16384, 4096),
    (128, 4, 16384, 4096),
    (128, 2, 5000, 8192),    # padded to whole selection blocks, as two blocks of 4096 were
    (128, 2, 12289, 16384),
    (128, 2, 300, 304),      # a short sequence: itself, padded to the sublanes
    (256, 4, 4096, 4096),    # within 4096 keys the block was and is the sequence, whatever its bytes
], ids=lambda x: str(x))
def test_default_kv_block_is_the_sequence_where_its_bytes_fit(D, itemsize, Sk, want):
    from ray_tpu.ops.flash import default_block_k

    assert default_block_k(Sk, D, itemsize) == want


@pytest.mark.parametrize("kernel", ["forward", "fused_backward"])
@pytest.mark.parametrize("D,itemsize,Sk,forward,fused_backward", [
    (128, 2, 4096, None, None),    # nine cells' kernels: Mosaic's default, the programs they were
    (128, 2, 8192, None, 33),      # keye / twotower / sdar: 24 + 1 + 8
    (256, 2, 4096, None, 34),      # glm47f-train: 24 + 2 + 8
    (128, 2, 16384, 25.25, 57),    # mellum2-train-16k (PR 56): 16 + 1.25 + 8 and 48 + 1 + 8
    (128, 4, 8192, 25.75, 49.75),
], ids=lambda x: str(x))
def test_a_kernel_states_its_vmem_only_where_its_blocks_pass_the_default(
        kernel, D, itemsize, Sk, forward, fused_backward):
    """`_fwd_params` / `_fused_bwd_params` at one kv block of `Sk` keys, one
    head of 512 rows a program, in MiB: None (no compiler parameter: the
    kernel the cells below 16,384 keys compiled before PR 56) where the
    blocks fit the 16 MiB Mosaic scopes by default, else the blocks + 8."""
    from ray_tpu.ops import flash

    fn, want = {"forward": (flash._fwd_params, forward),
                "fused_backward": (flash._fused_bwd_params, fused_backward)}[kernel]
    got = fn(512, flash.default_block_k(Sk, D, itemsize), D, 1, itemsize)
    assert (got and got.vmem_limit_bytes / 2 ** 20) == want


@pytest.mark.parametrize("bk,want", [(None, (1, 0)), (128, (0, 1))], ids=["fused", "split"])
def test_the_backward_counts_the_path_it_took_once_a_traced_call(backwards_traced, bk, want):
    """`flash.bwd_fused` / `flash.bwd_split`: one layer span a backward
    WHILE TRACING (chipbench's `fallback_sites.train` reads them); a call
    of the compiled function counts nothing more."""
    q, k, v = make_qkv(jax.random.key(0), 1, 256, 256, 2, 1, 32)
    grad = jax.jit(jax.grad(lambda q, k, v: flash_attention(q, k, v, block_q=128, block_k=bk).sum(),
                            argnums=(0, 1, 2)))
    assert backwards_traced(lambda: grad.lower(q, k, v)) == want
    compiled = grad.lower(q, k, v).compile()
    assert backwards_traced(lambda: jax.block_until_ready(compiled(q, k, v))) == (0, 0)
    forward = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=128, block_k=bk))
    assert backwards_traced(lambda: forward.lower(q, k, v)) == (0, 0)


@pytest.mark.parametrize("window,seg", [(None, False), (700, False), (None, True)],
                         ids=["causal", "window", "segments"])
def test_one_kv_block_over_4096_keys_matches_xla(backwards_traced, window, seg):
    """4224 keys in ONE kv block of 8192 (float32 at the 128 lanes: the 4 MiB
    the module's own budget admits since PR 56): seventeen sub-tiles a row
    block at most, the fused backward, value and all three gradients, with
    no selection."""
    from ray_tpu.ops import flash

    assert flash.default_block_k(4224, 32, 4) == 8192
    _one_kv_block_against_xla(backwards_traced, 4224, 2, window, seg)


@pytest.mark.parametrize("window", [None, 1024], ids=["causal", "window_1024"])
def test_one_kv_block_over_8192_keys_matches_xla(monkeypatch, backwards_traced, window):
    """PR 56, `mellum2-train-16k`'s walk at a size the lane affords: 8,320
    keys in ONE kv block of 12,288 = three selection blocks' width (float32
    at the 128 lanes: the budget raised to the 6 MiB that needs; bf16 at
    16,384 keys fits the module's own), two query heads a key head, the
    cell's window of 1,024 over sub-tiles of 512: up to seventeen sub-tiles
    a row block without the window and three or four under it, the fused
    backward, value and all three gradients."""
    from ray_tpu.ops import flash

    monkeypatch.setattr(flash, "KV_BLOCK_BYTES", 6 << 20)
    assert flash.default_block_k(8320, 32, 4) == 12288
    _one_kv_block_against_xla(backwards_traced, 8320, 2, window, False)


# ONE function object a reference: a fresh lambda a case would be a fresh compile a case
@functools.partial(jax.jit, static_argnames=("window",))
def _xla_value_and_grads(q, k, v, probe, segs, window):
    return jax.value_and_grad(lambda *a: (xla_attention(
        *a, causal=True, window=window, segment_ids=segs) * probe).sum(), (0, 1, 2))(q, k, v)


def _one_kv_block_against_xla(backwards_traced, S, H, window, seg):
    from ray_tpu.ops import flash

    B, KVH, D = 1, 1, 32
    q, k, v = make_qkv(jax.random.key(11), B, S, S, H, KVH, D)
    q = q * 0.5
    probe = jax.random.normal(jax.random.key(12), q.shape, jnp.float32)
    segs = jnp.broadcast_to((jnp.arange(S) >= 1500).astype(jnp.int32), (B, S)) if seg else None
    want = _xla_value_and_grads(q, k, v, probe, segs, window)
    got = []
    traced = backwards_traced(lambda: got.append(jax.value_and_grad(lambda *a: (flash.flash_attention(
        *a, causal=True, window=window, segment_ids=segs) * probe).sum(), (0, 1, 2))(q, k, v)))
    assert traced == (1, 0)
    assert float(got[0][0]) == pytest.approx(float(want[0]), rel=1e-4, abs=1e-3)
    for g, w, name in zip(got[0][1], want[1], "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3, atol=2e-4,
                                   err_msg=f"d{name}")
