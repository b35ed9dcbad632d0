"""Attention ops. XLA reference implementation + dispatch seam for Pallas kernels.

Grouped-query causal attention shaped for the MXU: contractions stay as
large einsums (bf16 in, fp32 softmax/accumulate) so XLA tiles them onto
the systolic array. `attention()` is the single entry point; `impl`
selects between the XLA composite (fused adequately by XLA for moderate
sequence lengths) and the Pallas flash kernel (ray_tpu.ops.flash).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _repeat_kv_heads(q: jax.Array, k: jax.Array) -> int:
    n_heads = q.shape[2]
    n_kv = k.shape[2]
    if n_heads % n_kv != 0:
        raise ValueError(f"n_heads {n_heads} not divisible by kv heads {n_kv}")
    return n_heads // n_kv


def xla_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, K, D]
    v: jax.Array,  # [B, Sk, K, D]
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, S] int, same for q/k when Sq==Sk
    q_offset: int | jax.Array = 0,
    softmax_scale: Optional[float] = None,
    window: Optional[int] = None,
    selection: Optional[jax.Array] = None,
    blockdiff: Optional[tuple] = None,
) -> jax.Array:
    """Reference GQA attention. fp32 softmax, bf16 matmuls. `window`: a
    row sees the `window` keys up to and including its own (sliding-window
    attention; a causal mask's). `selection`: which keys each row sees,
    the same for every head, packed as the flash kernels take it
    (ops/flash.py::pack_selection); ANDed with the other masks.
    `blockdiff` = (L, beta): the block-diffusion mask over 2L rows and 2L
    keys, a clean copy and a noised copy after it (`block_diffusion_mask`;
    `causal` then says nothing)."""
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    group = _repeat_kv_heads(q, k)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    if window is not None and not causal:
        raise ValueError("a sliding window is a causal mask's")

    qg = q.reshape(B, Sq, K, group, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale

    mask = None
    if blockdiff is not None:
        if (Sq, Sk) != (2 * blockdiff[0],) * 2 or window is not None or segment_ids is not None:
            raise ValueError(f"block diffusion {blockdiff}: 2L rows and keys, no window, no "
                             f"segments; got {Sq} x {Sk}")
        causal, mask = False, block_diffusion_mask(*blockdiff)[None, None, None]
    if causal:
        q_pos = jnp.arange(Sq)[:, None] + q_offset
        k_pos = jnp.arange(Sk)[None, :]
        mask = q_pos >= k_pos  # [Sq, Sk]
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        mask = mask[None, None, None, :, :]
    if segment_ids is not None:
        seg = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B, Sq, Sk]
        seg = seg[:, None, None, :, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if selection is not None:
        from ray_tpu.ops.flash import unpack_selection

        sel = unpack_selection(selection, Sk)[:, None, None, :, :]
        mask = sel if mask is None else jnp.logical_and(mask, sel)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])  # the values' own width, D where a head has one


def block_diffusion_mask(L: int, beta: int) -> jax.Array:
    """bool [2L, 2L], row sees key: rows and keys 0 .. L-1 are the clean
    copy of a sequence, L .. 2L-1 its noised copy (row L + i is position
    i), blocks of `beta` positions. Clean -> clean: the key's block is not
    after the row's (the row's own block whole: not causal inside it);
    clean -> noised: never; noised -> clean: the key's block is BEFORE the
    row's; noised -> noised: the same block, both ways."""
    at = jnp.arange(2 * L)
    noised, block = at >= L, (at % L) // beta
    rn, kn, rb, kb = noised[:, None], noised[None, :], block[:, None], block[None, :]
    return jnp.where(kn, rn & (kb == rb), jnp.where(rn, kb < rb, kb <= rb))


def _flash_over_mesh(q, k, v, segment_ids, *, head_axis: int = 2, **kw) -> jax.Array:
    """The flash kernel, one call per shard under an ambient mesh; the
    heads are axis `head_axis` of q, k, v and the result (2, or 1 for
    `attention_head_major`'s callers).

    A pallas_call has no GSPMD partitioning rule (the TPU compiler
    refuses to partition a Mosaic kernel), so on a multi-device mesh the
    kernel runs inside a shard_map: batch over the data axes and heads
    over the tensor axis, as the rules table lays them out. The sequence
    stays whole in every shard — splitting it is ring attention's job.

    Called from inside another shard_map (the pipeline's, manual over
    `pp`), only the axes that are still automatic are taken over; with
    none left the kernel is already per shard and runs as it is."""
    from ray_tpu.ops import flash
    from ray_tpu.parallel.context import current_mesh, current_rules

    flash_attention = flash.flash_attention if head_axis == 2 else flash.flash_attention_head_major
    mesh = current_mesh()
    manual = jax.sharding.get_abstract_mesh().manual_axes
    auto = frozenset() if mesh is None else frozenset(
        a for a in mesh.axis_names if a not in manual
    )
    if all(mesh.shape[a] == 1 for a in auto):
        return flash_attention(q, k, v, segment_ids=segment_ids, **kw)
    if kw.get("selection") is not None or kw.get("blockdiff") is not None:
        raise NotImplementedError(
            "a selection of keys or a block-diffusion mask under a multi-device mesh")
    rules = current_rules()
    qspec, kvspec = (
        rules.spec(tuple(heads if i == head_axis else axis
                         for i, axis in enumerate(("batch", None, None, None))))
        for heads in ("heads", "kv_heads"))
    args, in_specs = (q, k, v), (qspec, kvspec, kvspec)
    if segment_ids is not None:
        args += (segment_ids,)
        in_specs += (rules.spec(("batch", None)),)

    def per_shard(q, k, v, seg=None):
        return flash_attention(q, k, v, segment_ids=seg, **kw)

    # nested, the mesh has to be the context's own (it marks `manual`)
    return jax.shard_map(
        per_shard, mesh=None if manual else mesh, in_specs=in_specs,
        out_specs=qspec, axis_names=auto, check_vma=False,
    )(*args)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    q_offset: int | jax.Array = 0,
    softmax_scale: Optional[float] = None,
    impl: str = "xla",
) -> jax.Array:
    if impl == "xla":
        return xla_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            q_offset=q_offset, softmax_scale=softmax_scale,
        )
    if impl == "flash":
        return _flash_over_mesh(
            q, k, v, segment_ids, causal=causal, q_offset=q_offset,
            softmax_scale=softmax_scale,
        )
    if impl in ("ring", "ulysses"):
        # Context-parallel paths: sequence sharded over the mesh `sp` axis
        # (ray_tpu.ops.ring_attention). Mesh comes from the ambient
        # parallel_context. A missing context is an error, not a silent
        # fallback: the mesh is read at trace time and baked into the jit
        # cache, so "sometimes sharded" would pin whichever variant traced
        # first. (Enter parallel_context before tracing; sp == 1 meshes
        # degrade to the XLA composite inside ring_attention itself.)
        from ray_tpu.ops import ring_attention as ra
        from ray_tpu.parallel.context import current_mesh

        if not (isinstance(q_offset, int) and q_offset == 0):
            raise ValueError(
                f"attention(impl={impl!r}) is a full-sequence training path and "
                "does not support q_offset (decode with a KV cache uses "
                "impl='xla' or the paged kernel)"
            )
        mesh = current_mesh()
        if mesh is None:
            raise RuntimeError(
                f"attention(impl={impl!r}) needs an ambient mesh: wrap the "
                "call (before jit tracing) in "
                "ray_tpu.parallel.context.parallel_context(mesh)"
            )
        fn = ra.ring_attention if impl == "ring" else ra.ulysses_attention
        return fn(
            q, k, v, mesh=mesh, causal=causal, segment_ids=segment_ids,
            softmax_scale=softmax_scale,
        )
    raise ValueError(f"unknown attention impl {impl!r}")


def attention_head_major(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, K, S, D]
    v: jax.Array,  # [B, K, S, D]
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    impl: str = "xla",
    window: Optional[int] = None,
    selection: Optional[jax.Array] = None,
    blockdiff: Optional[tuple] = None,
) -> jax.Array:
    """`attention` for a caller whose heads are a major dimension, the
    tile (S, D): -> [B, H, S, D]. That is the flash kernels' own layout,
    so `impl="flash"` reaches them with no transpose on the way in or
    out; every other `impl` is `attention` between its transposes.
    `window` (None: every key before the row): sliding-window attention,
    which the flash kernels and the XLA composite implement; so with
    `selection` (None: none), the packed mask of a learned indexer
    (models/dsa.py); so with `blockdiff` (None: none), the block-diffusion
    mask over a clean and a noised copy (models/block_diffusion.py)."""
    masked = window is not None or selection is not None or blockdiff is not None
    if masked and impl not in ("flash", "xla"):
        raise ValueError(f"attention impl {impl!r} has no sliding window and no selection, "
                         "nor a block-diffusion mask")
    if impl == "flash":
        return _flash_over_mesh(q, k, v, segment_ids, head_axis=1, causal=causal,
                                window=window, selection=selection, blockdiff=blockdiff)
    if masked:
        o = xla_attention(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=causal,
                          segment_ids=segment_ids, window=window, selection=selection,
                          blockdiff=blockdiff)
        return jnp.swapaxes(o, 1, 2)
    o = attention(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=causal,
                  segment_ids=segment_ids, impl=impl)
    return jnp.swapaxes(o, 1, 2)
