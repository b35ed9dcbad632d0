"""Ring attention + Ulysses attention: context parallelism over the ICI ring.

The reference has no native sequence/context parallelism (SURVEY.md §5.7 —
long context is delegated to vLLM/torch inside workers). Here it is a
first-class op: sequences shard over the mesh `sp` axis and attention runs

  * **ring**: K/V blocks rotate around the `sp` axis with
    `jax.lax.ppermute` while each device accumulates blockwise
    softmax(QK^T)V online (flash-attention-style running max/sum, fp32
    accumulators). One block of K/V is in flight per step, so the
    `ppermute` rides ICI concurrently with the MXU matmuls of the
    current block — compute/communication overlap falls out of XLA's
    async collective scheduling rather than hand-written double
    buffering.
  * **ulysses**: `jax.lax.all_to_all` swaps the sharded axis from
    sequence to heads, runs ordinary full attention locally, and swaps
    back. Cheaper for moderate sequence lengths when n_heads % sp == 0.

Both are SPMD-inner functions meant to run inside `jax.shard_map`; the
`ring_attention` / `ulysses_attention` wrappers build the shard_map over
the framework mesh (batch over (dp, fsdp), heads over tp, sequence over
sp). Gradients flow through `ppermute`/`all_to_all` transposes, so the
same code paths serve training.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash import NEG_INF as FLASH_NEG_INF, flash_attention


def ring_attention_spmd(
    q: jax.Array,  # [B, Sq_local, H, D]  (local sequence shard)
    k: jax.Array,  # [B, Sk_local, K, D]
    v: jax.Array,  # [B, Sk_local, K, D]
    *,
    axis_name: str = "sp",
    causal: bool = True,
    kv_segment_ids: Optional[jax.Array] = None,  # [B, Sk_local]
    q_segment_ids: Optional[jax.Array] = None,  # [B, Sq_local]
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention body. Call inside shard_map with seq sharded on axis_name.

    Sequence is assumed contiguously sharded: device i holds global
    positions [i*S_local, (i+1)*S_local). Causal masking is applied on
    global positions, so the result equals full-sequence causal attention.
    """
    if q_segment_ids is None and kv_segment_ids is not None:
        q_segment_ids = kv_segment_ids
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)

    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    # kv arrives from the next-higher rank each step: after t rotations the
    # local buffer holds block (my + t) mod n.
    perm = [(i, (i - 1) % n) for i in range(n)]

    # Per-block compute is the FLASH kernel (ops/flash.py) returning
    # (o, lse); blocks merge through log-sum-exp. A raw-XLA
    # online-softmax body here was far slower than flash on the chip — the
    # ring's job is rotation + merge, the MXU work belongs in the kernel.
    def flash_block(k_cur, v_cur, seg_cur, *, block_causal: bool):
        kw = {}
        if seg_cur is not None:
            kw = {"segment_ids": q_segment_ids, "kv_segment_ids": seg_cur}
        return flash_attention(
            q, k_cur, v_cur, causal=block_causal, softmax_scale=scale,
            return_lse=True, **kw,
        )

    def compute_block(k_cur, v_cur, seg_cur, src):
        # diagonal block (src == my): causal within the block; blocks
        # strictly behind (src < my): full attention. Both are compiled;
        # the traced src picks one. (Non-causal rings are all "full".)
        if not causal:
            return flash_block(k_cur, v_cur, seg_cur, block_causal=False)
        return jax.lax.cond(
            src == my,
            lambda kc, vc: flash_block(kc, vc, seg_cur, block_causal=True),
            lambda kc, vc: flash_block(kc, vc, seg_cur, block_causal=False),
            k_cur, v_cur,
        )

    def merge(o_run, lse_run, o_t, lse_t):
        m = jnp.maximum(lse_run, lse_t)
        w1 = jnp.exp(lse_run - m)
        w2 = jnp.exp(lse_t - m)
        denom = w1 + w2
        o = (
            o_run * w1[..., None] + o_t.astype(jnp.float32) * w2[..., None]
        ) / denom[..., None]
        return o, m + jnp.log(denom)

    def masked_compute(o_run, lse_run, k_cur, v_cur, seg_cur, src):
        if causal:
            # blocks strictly in the future (src > my under contiguous
            # sharding) are fully masked — skip their matmuls entirely.
            # Average saving is ~2x attention FLOPs at large sp; the
            # remaining rank imbalance is the known cost of contiguous
            # sharding (zigzag layouts would balance it).
            def skip(*_):
                return o_run, lse_run

            def run(kc, vc):
                o_t, lse_t = compute_block(kc, vc, seg_cur, src)
                return merge(o_run, lse_run, o_t, lse_t)

            return jax.lax.cond(src > my, skip, run, k_cur, v_cur)
        o_t, lse_t = compute_block(k_cur, v_cur, seg_cur, src)
        return merge(o_run, lse_run, o_t, lse_t)

    def body(carry, t):
        o, lse, k_cur, v_cur, seg_cur = carry
        o, lse = masked_compute(o, lse, k_cur, v_cur, seg_cur, (my + t) % n)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        seg_nxt = (
            jax.lax.ppermute(seg_cur, axis_name, perm) if seg_cur is not None else None
        )
        return (o, lse, k_nxt, v_nxt, seg_nxt), None

    o0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    lse0 = jnp.full((B, Sq, H), FLASH_NEG_INF, jnp.float32)
    # n-1 rotations in the scan; the last block needs no onward ppermute,
    # so it is folded in as an epilogue (saves one dead KV rotation).
    (o, lse, k_last, v_last, seg_last), _ = jax.lax.scan(
        body, (o0, lse0, k, v, kv_segment_ids), jnp.arange(n - 1)
    )
    o, _ = masked_compute(o, lse, k_last, v_last, seg_last, (my + n - 1) % n)
    return o.astype(q.dtype)


def ulysses_attention_spmd(
    q: jax.Array,  # [B, S_local, H, D]
    k: jax.Array,  # [B, S_local, K, D]
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, S_local]
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """All-to-all head/sequence swap: full attention runs locally per head group."""
    n = jax.lax.axis_size(axis_name)
    H, Kh = q.shape[2], k.shape[2]
    if H % n or Kh % n:
        raise ValueError(f"ulysses needs heads ({H}/{Kh}) divisible by axis size {n}")
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    qf, kf, vf = a2a(q), a2a(k), a2a(v)  # [B, S_full, H/n, D]
    seg_full = (
        jax.lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
        if segment_ids is not None
        else None
    )
    # local full-sequence attention runs the FLASH kernel (2-3x XLA
    # attention on v5e at these shapes; ring took the same step round 5)
    o = flash_attention(
        qf, kf, vf, causal=causal, segment_ids=seg_full,
        softmax_scale=softmax_scale,
    )
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2, tiled=True)


def _cp_wrapper(spmd_fn, seg_kwargs):
    """Shared shard_map wrapper for both context-parallel variants.

    seg_kwargs maps one segment-ids array to the spmd fn's kwarg name(s).
    """

    def wrapper(
        q: jax.Array,  # [B, S, H, D]  (global shapes; sharding via shard_map)
        k: jax.Array,
        v: jax.Array,
        *,
        mesh: Mesh,
        axis: str = "sp",
        causal: bool = True,
        segment_ids: Optional[jax.Array] = None,
        softmax_scale: Optional[float] = None,
        batch_axes=("dp", "fsdp"),
        heads_axis: str = "tp",
    ) -> jax.Array:
        if mesh.shape[axis] == 1:
            # sp=1 degrades to the XLA composite, NOT the flash kernel:
            # this call sits OUTSIDE shard_map on global arrays, and a
            # pallas_call has no GSPMD partitioning rule — on a dp/tp
            # mesh XLA would replicate it (all-gathering the batch)
            # instead of partitioning like the composite does
            return xla_attention(
                q, k, v, causal=causal, segment_ids=segment_ids,
                softmax_scale=softmax_scale,
            )
        qspec = P(batch_axes, axis, heads_axis, None)
        in_specs = (qspec, qspec, qspec)
        args = (q, k, v)
        if segment_ids is not None:
            in_specs += (P(batch_axes, axis),)
            args += (segment_ids,)

        def inner(q, k, v, *maybe_seg):
            kw = {name: maybe_seg[0] for name in seg_kwargs} if maybe_seg else {}
            return spmd_fn(
                q, k, v, axis_name=axis, causal=causal,
                softmax_scale=softmax_scale, **kw,
            )

        return jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=qspec, check_vma=False
        )(*args)

    return wrapper


ring_attention = _cp_wrapper(ring_attention_spmd, ("kv_segment_ids", "q_segment_ids"))
ring_attention.__name__ = "ring_attention"
ring_attention.__doc__ = (
    'Context-parallel causal attention over mesh axis `axis` (default "sp").'
)
ulysses_attention = _cp_wrapper(ulysses_attention_spmd, ("segment_ids",))
ulysses_attention.__name__ = "ulysses_attention"
ulysses_attention.__doc__ = "All-to-all (Ulysses) context-parallel attention."
