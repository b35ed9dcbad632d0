"""Ragged paged attention over a flat-slot KV cache.

One kernel serving MIXED prefill+decode batches (ragged paged
attention lineage, PAPERS.md arxiv 2604.15464): queries arrive PACKED
— variable-length rows concatenated along one token axis, delimited by
`cu_q_lens` — so a batch mixing in-flight prefill chunks (q_len up to
the chunk budget) and decode rows (q_len=1) runs as ONE program with
zero per-row bucket padding. A decode-only batch is the degenerate
case (all q_len=1, T == B) and reduces to `ops/paged_attention.py`'s
cost; spec verify's all-position logits are the ragged case proper
(q_len = 1 + draft_len per row).

Two implementations, following the `ops/paged_attention.py` precedent:

 * `ragged_attention_xla` — gather + masked softmax, pure XLA.
   Portable (CPU tests), and the identity oracle: its einsum structure
   mirrors `paged_attention_xla` / `_page_attend_prefill` so the mixed
   engine path stays bitwise token-identical to the split path.
 * `ragged_attention_pallas` — Pallas kernel, one grid step per
   (kv-head, sequence, page): block-table rows + `cu_q_lens` +
   `context_lens` are scalar-prefetched (SMEM) so the pipeline DMAs
   exactly the pages each sequence needs, fp32 online softmax, GQA by
   folding query heads into the packed row axis on the host.
   `interpret=` is plumbed through like `ops/flash.py` so CPU CI
   executes the real kernel body.

Layout (see llm/kv_cache.py): k_cache/v_cache are HEAD-MAJOR
[n_kv_heads, num_slots, head_dim] PER LAYER; slot = block_id *
block_size + offset. Query row j of sequence b sits at packed index
cu_q_lens[b] + j and attends positions <= context_lens[b] - q_len_b + j
(absolute causal over its own pages).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import NEG_INF


def ragged_attention_xla(
    q: jax.Array,            # [T, n_heads, head_dim] packed query rows
    k_cache: jax.Array,      # [n_kv_heads, num_slots, head_dim]
    v_cache: jax.Array,      # [n_kv_heads, num_slots, head_dim]
    block_tables: jax.Array, # [B, max_blocks] int32 block ids (padded w/ 0)
    cu_q_lens: jax.Array,    # [B+1] int32 exclusive prefix sums of q lens
    context_lens: jax.Array, # [B] int32 valid kv tokens per sequence
    *,
    block_size: int,
) -> jax.Array:              # [T, n_heads, head_dim]
    T, H, D = q.shape
    KVH = k_cache.shape[0]
    G = H // KVH  # query heads per kv head (GQA group)
    B = context_lens.shape[0]
    MB = block_tables.shape[1]
    S = MB * block_size  # padded kv length

    # packed row -> owning sequence; rows past cu_q_lens[B] are padding
    # and clip to sequence B-1 (their outputs are ignored by callers)
    t = jnp.arange(T, dtype=jnp.int32)
    seq_id = jnp.clip(
        jnp.searchsorted(cu_q_lens, t, side="right") - 1, 0, B - 1
    )
    q_lens = (cu_q_lens[1:] - cu_q_lens[:B]).astype(jnp.int32)  # [B]
    # absolute causal position of each packed query row
    q_pos = (
        context_lens[seq_id] - q_lens[seq_id] + (t - cu_q_lens[seq_id])
    )  # [T]

    # slot indices for every (sequence, position): [B, S]
    offs = jnp.arange(S, dtype=jnp.int32)
    slots = block_tables[:, offs // block_size] * block_size + offs % block_size
    k = k_cache[:, slots][:, seq_id]  # [KVH, T, S, D] (head-major cache)
    v = v_cache[:, slots][:, seq_id]

    qg = q.reshape(T, KVH, G, D).astype(jnp.float32)
    scores = jnp.einsum("thgd,htsd->thgs", qg, k.astype(jnp.float32))
    scores *= 1.0 / jnp.sqrt(D).astype(jnp.float32)
    valid = (offs[None, :] <= q_pos[:, None]) & (
        offs[None, :] < context_lens[seq_id][:, None]
    )  # [T, S]
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked pad rows
    out = jnp.einsum("thgs,htsd->thgd", probs, v.astype(jnp.float32))
    return out.reshape(T, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _ragged_attn_kernel(
    # scalar-prefetch
    cu_q_lens_ref,     # [B+1] SMEM
    context_lens_ref,  # [B] SMEM
    block_tables_ref,  # [B, MB] SMEM
    # inputs (blocked by grid; the PIPELINE fetches this (h, b, i)'s
    # page — the page index map reads the prefetched block table, so
    # the kernel DMAs exactly the pages sequence b owns)
    q_ref,       # [1, TG_pad, D] VMEM — kv head h's packed query rows
    k_ref,       # [1, 1, block_size, D] VMEM — page bt[b, i] of kv head h
    v_ref,
    # output
    o_ref,       # [1, TG_pad, D] VMEM (revisited across the whole h slice)
    # scratch
    acc_ref,     # [W, D] fp32 — W = the aligned row window (see below)
    m_ref,       # [W, 128] running max
    l_ref,       # [W, 128] running denom
    *,
    block_size: int,
    group: int,  # G: query heads folded per kv head
    tile: int,   # sublane tile of q's dtype: window starts are multiples of it
):
    from jax.experimental import pallas as pl

    b = pl.program_id(1)
    i = pl.program_id(2)  # page index within this sequence
    n_pages = pl.num_programs(2)
    W, D = acc_ref.shape

    @pl.when((b == 0) & (i == 0))
    def _():
        # first visit of this head's output block: zero it once — the
        # per-sequence finalize below only writes its own valid rows
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = context_lens_ref[b]
    q_start = cu_q_lens_ref[b] * group
    q_len = cu_q_lens_ref[b + 1] - cu_q_lens_ref[b]
    # Mosaic only takes a dynamic sublane slice whose start it can prove
    # tile-aligned, and cu_q_lens[b] * G is an arbitrary run-time row. So
    # the window starts at the aligned row at or below q_start and is one
    # tile wider than MAXQ*G; the sequence's rows sit `lead` rows in.
    w_start = pl.multiple_of((q_start // tile) * tile, tile)
    lead = q_start - w_start

    # window row r is folded row r - lead of this sequence, i.e. query
    # j = (r - lead) // group at absolute causal position ctx - q_len + j;
    # rows before `lead` or at/after q_len * group belong to neighbours
    row = jax.lax.broadcasted_iota(jnp.int32, (W, block_size), 0) - lead
    mine = (row >= 0) & (row < q_len * group)
    row_q = jnp.where(mine, row, 0) // group

    @pl.when((i * block_size < ctx) & (q_len > 0))
    def _():
        q = q_ref[0, pl.ds(w_start, W)].astype(jnp.float32) * (
            1.0 / (D ** 0.5)
        )  # [W, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(  # [W, bs]
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        kv_pos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (W, block_size), 1
        )
        q_pos = ctx - q_len + row_q
        ok = (kv_pos <= q_pos) & (kv_pos < ctx) & mine
        s = jnp.where(ok, s, NEG_INF)

        # online softmax update
        m_prev = m_ref[:, :1]                      # [W, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                     # [W, bs]
        alpha = jnp.exp(m_prev - m_new)            # [W, 1]
        l_new = alpha * l_ref[:, :1] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when((i == n_pages - 1) & (q_len > 0))
    def _():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        vals = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        # masked read-modify-write: the window overlaps the neighbouring
        # sequences' rows on both sides — rows that are not this
        # sequence's keep their current contents. Safe because the
        # output block stays VMEM-resident for the whole (b, i) sweep of
        # this head.
        cur = o_ref[0, pl.ds(w_start, W)]
        o_ref[0, pl.ds(w_start, W)] = jnp.where(mine[:, :1], vals, cur)


def ragged_attention_pallas(
    q: jax.Array,            # [T, n_heads, head_dim] packed query rows
    k_cache: jax.Array,      # [n_kv_heads, num_slots, head_dim]
    v_cache: jax.Array,
    block_tables: jax.Array, # [B, max_blocks]
    cu_q_lens: jax.Array,    # [B+1]
    context_lens: jax.Array, # [B]
    *,
    block_size: int,
    max_q_len: int,
    interpret: bool = False,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, D = q.shape
    KVH = k_cache.shape[0]
    G = H // KVH
    B = context_lens.shape[0]
    MB = block_tables.shape[1]
    num_slots = k_cache.shape[1]
    if num_slots % block_size:
        raise ValueError(
            f"cache slots {num_slots} not a multiple of block_size {block_size}"
        )
    if max_q_len < 1:
        raise ValueError(f"max_q_len must be >= 1, got {max_q_len}")
    # sublane tile of q's dtype (8 rows fp32, 16 bf16): the kernel's row
    # window starts on one and spans W = MAXQ*G rounded up, plus the tile
    # it may have stepped back to get aligned
    tile = 8 * max(1, 4 // q.dtype.itemsize)
    W = -(-max_q_len * G // tile) * tile + tile

    # GQA folded on the HOST: [T, H, D] -> [KVH, T*G, D] so sequence
    # b's rows occupy the contiguous window [cu[b]*G, cu[b+1]*G) of one
    # clean 2D MXU operand per kv head — no in-kernel reshape. The row
    # axis is padded to a tile multiple plus W rows so the kernel's
    # fixed-size slice q[w_start : w_start + W] never runs off the end
    # for the last sequence.
    qf = q.reshape(T, KVH, G, D).swapaxes(0, 1).reshape(KVH, T * G, D)
    TG_pad = -(-T * G // tile) * tile + W
    qf = jnp.pad(qf, ((0, 0), (0, TG_pad - T * G), (0, 0)))

    # caches viewed pre-blocked [KVH, num_blocks, block_size, D]: each
    # grid step's index map picks page bt[b, i] straight from the
    # scalar-prefetched block table
    kp = k_cache.reshape(KVH, num_slots // block_size, block_size, D)
    vp = v_cache.reshape(KVH, num_slots // block_size, block_size, D)

    def q_index(h, b, i, cu, cl, bt):
        return (h, 0, 0)

    def page_index(h, b, i, cu, cl, bt):
        # pages past the context read page bt[b, padding]=0 and are
        # skipped in-kernel; the table is padded with block 0
        return (h, bt[b, i], 0, 0)

    # grid: kv head OUTERMOST so the output block (whose index map
    # depends only on h) stays VMEM-resident across the whole
    # (sequence, page) sweep — the per-sequence finalize is a masked
    # read-modify-write into that resident block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(KVH, B, MB),
        in_specs=[
            pl.BlockSpec((1, TG_pad, D), q_index),
            pl.BlockSpec((1, 1, block_size, D), page_index),
            pl.BlockSpec((1, 1, block_size, D), page_index),
        ],
        out_specs=pl.BlockSpec((1, TG_pad, D), q_index),
        scratch_shapes=[
            pltpu.VMEM((W, D), jnp.float32),
            pltpu.VMEM((W, 128), jnp.float32),
            pltpu.VMEM((W, 128), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _ragged_attn_kernel, block_size=block_size, group=G, tile=tile
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KVH, TG_pad, D), q.dtype),
        interpret=interpret,
    )
    out = kernel(
        cu_q_lens.astype(jnp.int32), context_lens.astype(jnp.int32),
        block_tables.astype(jnp.int32), qf, kp, vp,
    )
    # unfold the host-side GQA packing: [KVH, T*G, D] -> [T, H, D]
    out = out[:, : T * G].reshape(KVH, T, G, D).swapaxes(0, 1)
    return out.reshape(T, H, D)


def ragged_attention(
    q, k_cache, v_cache, block_tables, cu_q_lens, context_lens, *,
    block_size: int, max_q_len: int, impl: str = "auto",
):
    """impl: auto | xla | pallas | pallas_interpret.

    auto = xla everywhere, for the same reason as `paged_attention`:
    the gather + masked softmax is a dynamic-slice stream XLA pipelines
    well, while the one-page-per-program kernel's DMA overhead
    dominates at decode-heavy shapes. The Pallas kernel stays available
    for long-prefill-heavy mixes (where one sequence touches many
    pages and the XLA gather materializes [T, S, D]) and as the Mosaic
    reference; `max_q_len` is its static row-window bucket — every
    sequence's q_len must be <= max_q_len (the mixed-batch planner
    guarantees this by construction).
    """
    if impl == "auto":
        impl = "xla"
    if impl == "xla":
        return ragged_attention_xla(
            q, k_cache, v_cache, block_tables, cu_q_lens, context_lens,
            block_size=block_size,
        )
    if impl == "pallas":
        return ragged_attention_pallas(
            q, k_cache, v_cache, block_tables, cu_q_lens, context_lens,
            block_size=block_size, max_q_len=max_q_len,
        )
    if impl == "pallas_interpret":
        return ragged_attention_pallas(
            q, k_cache, v_cache, block_tables, cu_q_lens, context_lens,
            block_size=block_size, max_q_len=max_q_len, interpret=True,
        )
    raise ValueError(f"unknown ragged attention impl {impl!r}")
