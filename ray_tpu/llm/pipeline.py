"""Single-dispatch pipelined decode: device-resident batch state,
on-device stop masks, async double-buffered chunks, adaptive chunk
length.

The r06 decode profile prices ``sampling`` (~36%) and ``host_sync`` as
the dominant non-matmul segments of a decode step, and the r08 traces
show every chunk round-trip ending in a blocking ``np.asarray`` sync
plus a full rebuild + re-upload of the batch arrays from numpy. This
module removes all four taxes from the serving hot loop:

 * **DeviceBatchState** — tokens / positions / context_lens / block
   tables / sampling knobs / PRNG keys / stop sets live ON DEVICE
   across chunks and are re-materialized only at membership changes
   (join / finish / preempt / import_handoff), not every round;
 * **on-device stop masks** — ``decode_chunk_masked`` carries a per-row
   ``done`` mask folding EOS, bounded stop-id sets, max_tokens and the
   max_seq wall in-graph: finished rows freeze (trash-slot KV writes,
   masked sampling outputs, no position advance past the RoPE table)
   and a ``lax.while_loop`` early-out stops the whole chunk once every
   row is done — a batch that finishes at step 1 of a 16-step chunk
   does not pay the other 15;
 * **async double-buffered dispatch** — the engine dispatches chunk
   N+1 from the device-resident carry BEFORE syncing chunk N's tokens
   (JAX async dispatch), so host-side detokenize / stop bookkeeping /
   SLO spans / admission overlap device compute;
 * **ChunkController** — chunk length is driven from the measured
   per-round host gap and per-step device time, quantized to
   CHUNK_BUCKETS so the engine's jit cache stays bounded, replacing
   the hand-picked ``decode_chunk=8/16``.

Correctness contract: the pipelined path produces bitwise-identical
token streams to the sync path (greedy and seeded sampling, including
stop-token and max_tokens terminations) — sampling keys remain a pure
function of (request key, absolute output index), and the host stop
ladder in ``_append_chunk`` walks exactly the per-row ``n_emitted``
tokens the device kept. "Exploring the limits of Concurrency in ML
Training on Google TPUs" (PAPERS.md) is the blueprint: hide host
latency behind device work and never let the host gate the chip.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.sampling import sample_tokens
from ray_tpu.models.llama_decode import decode_step

# the ONLY chunk lengths the engine may compile: the adaptive controller
# quantizes into this set and LLMEngine asserts membership, so the
# (n_steps, mode) jit cache is bounded by construction instead of
# growing with every novel chunk length
CHUNK_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

# stop-id sets are carried on device as a padded [B, stop_w] matrix;
# widths are bucketed (compile-shape bounding) and capped — a request
# with more stop ids than the cap falls back to the sync decode path
STOP_WIDTHS = (1, 2, 4, 8)
STOP_WIDTH_CAP = STOP_WIDTHS[-1]


def chunk_bucket(n: int, cap: Optional[int] = None) -> int:
    """Smallest CHUNK_BUCKETS entry >= n; with ``cap``, never larger
    than the smallest bucket covering the cap (steps past every row's
    budget are pure waste). Always a valid compile bucket."""
    pick = next((b for b in CHUNK_BUCKETS if b >= n), CHUNK_BUCKETS[-1])
    if cap is not None:
        capb = next(
            (b for b in CHUNK_BUCKETS if b >= max(1, cap)), CHUNK_BUCKETS[-1]
        )
        pick = min(pick, capb)
    return pick


def stop_width(n: int) -> int:
    """Smallest STOP_WIDTHS entry >= max(1, n); caller must have
    checked n <= STOP_WIDTH_CAP."""
    for w in STOP_WIDTHS:
        if w >= max(1, n):
            return w
    raise ValueError(
        f"stop set width {n} exceeds STOP_WIDTH_CAP={STOP_WIDTH_CAP}"
    )


# ---------------------------------------------------------------------------
# observability: the /v1/stats row (per round, the same two times are the
# engine's layer spans engine.decode_dispatch and engine.sync)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PipelineStats:
    """Pipelined-decode counters for the ``pipeline`` row of
    ``/v1/stats`` (the serving-side view, no Prometheus scrape needed):
    chunk-size distribution, host/device time split, overlap ratio, and
    the device steps the early-out actually skipped."""

    dispatches: int = 0
    syncs: int = 0
    rebuilds: int = 0
    flushes: int = 0
    sync_fallbacks: int = 0           # wide-stop-set batches
    steps_dispatched: int = 0         # sum of n_steps over chunks
    steps_executed: int = 0           # sum of while_loop exits (early-out)
    host_prep_ms: float = 0.0         # overlapped host work
    sync_wait_ms: float = 0.0         # un-hidden sync block
    chunk_ms: float = 0.0             # dispatch -> sync wall
    chunks_by_steps: dict = dataclasses.field(default_factory=dict)

    def record_dispatch(self, n_steps: int, host_prep_ms: float) -> None:
        self.dispatches += 1
        self.steps_dispatched += n_steps
        self.host_prep_ms += host_prep_ms
        self.chunks_by_steps[n_steps] = self.chunks_by_steps.get(n_steps, 0) + 1

    def record_sync(self, *, steps_run: int, sync_wait_ms: float,
                    chunk_ms: float) -> None:
        self.syncs += 1
        self.steps_executed += steps_run
        self.sync_wait_ms += sync_wait_ms
        self.chunk_ms += chunk_ms

    @property
    def overlap_ratio(self) -> float:
        """Fraction of per-round host time hidden under device compute:
        prep / (prep + un-hidden sync wait)."""
        total = self.host_prep_ms + self.sync_wait_ms
        return self.host_prep_ms / total if total > 0 else 0.0

    def to_dict(self) -> dict:
        return {
            "dispatches": self.dispatches,
            "syncs": self.syncs,
            "rebuilds": self.rebuilds,
            "flushes": self.flushes,
            "sync_fallbacks": self.sync_fallbacks,
            "chunks_by_steps": dict(sorted(self.chunks_by_steps.items())),
            "steps_dispatched": self.steps_dispatched,
            "steps_executed": self.steps_executed,
            "steps_saved_by_early_exit": max(
                0, self.steps_dispatched - self.steps_executed
            ),
            "host_prep_ms": round(self.host_prep_ms, 3),
            "sync_wait_ms": round(self.sync_wait_ms, 3),
            "chunk_ms": round(self.chunk_ms, 3),
            "overlap_ratio": round(self.overlap_ratio, 4),
        }


# ---------------------------------------------------------------------------
# adaptive chunk length
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ChunkController:
    """Measured-gap-adaptive chunk length (a ratchet, not a formula).

    The signal pair: per-round HOST OVERHEAD (the r08 ``sched_gap_ms``
    between a sync landing and the next dispatch, plus the un-hidden
    sync wait) versus the measured chunk wall (dispatch to sync). A
    chunk must be
    long enough that overhead hides under device compute with
    ``target_ratio`` headroom — when it isn't, step up one bucket. The
    only downward pressure is SYSTEMATIC early exit (the while_loop
    retiring under half the dispatched steps on consecutive chunks:
    the batch keeps finishing long before the chunk does, so shorter
    chunks cut reserved-KV churn at zero throughput cost).

    Deliberately NOT ``n = overhead/step_cost``: per-step cost measured
    at one chunk length conflates fixed dispatch overhead with marginal
    step cost and collapses to 1-step chunks on hosts where dispatch
    dominates — the exact regime chunking exists to amortize.

    The decision is a pure function of the fed measurements
    (EMA-smoothed), so a fixed gap/chunk trace replays to a
    deterministic bucket sequence, and every output is quantized to
    CHUNK_BUCKETS so the engine's jit cache stays bounded."""

    initial: int = 8
    target_ratio: float = 2.0
    alpha: float = 0.3                 # EMA smoothing
    shrink_frac: float = 0.5           # early-exit threshold
    shrink_patience: int = 2           # consecutive short chunks to shrink
    chunk_ms_ema: Optional[float] = None
    overhead_ms_ema: Optional[float] = None
    _level: Optional[int] = None       # index into CHUNK_BUCKETS
    _short_rounds: int = 0

    def _lvl(self) -> int:
        if self._level is None:
            self._level = CHUNK_BUCKETS.index(chunk_bucket(max(1, self.initial)))
        return self._level

    def note_overhead(self, ms: float) -> None:
        ms = max(0.0, float(ms))
        self.overhead_ms_ema = (
            ms if self.overhead_ms_ema is None
            else (1 - self.alpha) * self.overhead_ms_ema + self.alpha * ms
        )

    def note_chunk(self, chunk_ms: float, n_steps: int,
                   steps_run: Optional[int] = None) -> None:
        if n_steps <= 0 or chunk_ms <= 0:
            return
        self.chunk_ms_ema = (
            chunk_ms if self.chunk_ms_ema is None
            else (1 - self.alpha) * self.chunk_ms_ema + self.alpha * chunk_ms
        )
        lvl = self._lvl()
        if (
            self.overhead_ms_ema is not None
            and self.chunk_ms_ema < self.target_ratio * self.overhead_ms_ema
        ):
            # device work too short to hide the host round: step up
            self._level = min(lvl + 1, len(CHUNK_BUCKETS) - 1)
            self._short_rounds = 0
            return
        if steps_run is not None and steps_run < self.shrink_frac * n_steps:
            self._short_rounds += 1
            if self._short_rounds >= self.shrink_patience:
                self._level = max(lvl - 1, 0)
                self._short_rounds = 0
        else:
            self._short_rounds = 0

    def next_steps(self, cap: Optional[int] = None) -> int:
        """Chunk length for the next dispatch, in CHUNK_BUCKETS.
        ``cap`` bounds it (e.g. the batch's largest remaining token
        budget — steps past every row's budget are pure waste)."""
        return chunk_bucket(CHUNK_BUCKETS[self._lvl()], cap)


# ---------------------------------------------------------------------------
# device-resident batch state
# ---------------------------------------------------------------------------


def assemble_batch_arrays(batch: list, B_pad: int, bt_width: int):
    """Per-row decode-batch assembly: the SINGLE source of truth for
    how a Request becomes batch-array rows (fed token, position,
    context length, sampling knobs, key, absolute output index, block
    table). Both the sync path (LLMEngine._plain_decode_step) and
    DeviceBatchState.build consume this — the pipelined-vs-sync bitwise
    token-identity contract depends on the two paths never drifting,
    so neither keeps its own copy.

    Returns (arrays dict of np arrays, keys list of per-request PRNG
    keys). Pad rows: context_lens 0 (the kernels' pad/done signal),
    temperature 1, top_p 1, max_tokens INT32_MAX, key(0)."""
    a = {
        "tokens": np.zeros(B_pad, np.int32),
        "positions": np.zeros(B_pad, np.int32),
        "context_lens": np.zeros(B_pad, np.int32),
        "lora_ids": np.zeros(B_pad, np.int32),
        "temps": np.ones(B_pad, np.float32),
        "top_ks": np.zeros(B_pad, np.int32),
        "top_ps": np.ones(B_pad, np.float32),
        "starts": np.zeros(B_pad, np.int32),
        "max_toks": np.full(B_pad, np.iinfo(np.int32).max, np.int32),
        "bt": np.zeros((B_pad, bt_width), np.int32),
    }
    keys = [jax.random.key(0)] * B_pad
    for i, r in enumerate(batch):
        sp = r.sampling_params
        a["tokens"][i] = (
            r.output_token_ids[-1] if r.output_token_ids
            else r.prompt_token_ids[-1]
        )
        a["positions"][i] = r.num_tokens - 1  # position of the fed token
        a["context_lens"][i] = r.num_tokens
        a["lora_ids"][i] = r.lora_slot
        a["temps"][i] = sp.temperature
        a["top_ks"][i] = sp.top_k
        a["top_ps"][i] = sp.top_p
        a["starts"][i] = len(r.output_token_ids)
        a["max_toks"][i] = sp.max_tokens
        a["bt"][i, : len(r.seq.blocks)] = r.seq.blocks
        keys[i] = r._key
    return a, keys


@dataclasses.dataclass
class DeviceBatchState:
    """The decode batch, resident on device across chunks.

    Built once per membership change (the old per-round numpy rebuild +
    ``jnp.asarray``/``jnp.stack`` upload, amortized); between chunks
    only the carry (tokens / positions / context_lens / done / starts)
    is swapped — device arrays returned by the previous chunk, no host
    transfer — and the block-table mirror re-uploads only when a row
    actually grew. Rows that finish keep their column as permanently
    ``done`` rows (trash-slot writes, zero emissions) until the next
    rebuild, which is what lets chunk N+1 dispatch before chunk N's
    finishes are even known host-side."""

    rids: list
    row_of: dict
    B: int
    B_pad: int
    bt_width: int
    stop_w: int
    sample_mode: str
    # device-resident carry (updated from each chunk's return)
    tokens: Any = None
    positions: Any = None
    context_lens: Any = None
    done: Any = None
    starts: Any = None
    # device-resident per-request constants
    temps: Any = None
    top_ks: Any = None
    top_ps: Any = None
    keys: Any = None
    max_toks: Any = None
    stop_ids: Any = None
    stop_on_eos: Any = None
    lora_ids: Any = None
    block_tables: Any = None
    # host mirrors (block-table refresh without a device round trip)
    _bt_np: Any = None
    _nblocks: list = dataclasses.field(default_factory=list)

    @classmethod
    def build(cls, engine, batch: list, shape: Optional[tuple] = None,
              ) -> "DeviceBatchState":
        """``shape`` = (B_pad, block-table width, stop width, sample
        mode) overrides what the batch would select: warmup() builds the
        state of an empty batch at every shape of the tables."""
        c = engine.config
        B = len(batch)
        if shape is not None:
            B_pad, btw, sw, sample_mode = shape
        else:
            B_pad = engine._pad_to_bucket(B, c.decode_buckets())
            btw = engine._bt_width([len(r.seq.blocks) for r in batch])
            sw = stop_width(max(
                (len(r.sampling_params.stop_token_ids) for r in batch), default=0
            ))
            sample_mode = engine._sample_mode(batch)
        a, keys = assemble_batch_arrays(batch, B_pad, btw)
        # pipeline-only rows the sync path evaluates host-side instead:
        # the padded stop-id sets and the per-row EOS policy
        stop_ids = np.full((B_pad, sw), -1, np.int32)
        stop_on_eos = np.zeros(B_pad, bool)
        nblocks = [0] * B_pad
        for i, r in enumerate(batch):
            sp = r.sampling_params
            for j, t in enumerate(sp.stop_token_ids[:sw]):
                stop_ids[i, j] = t
            stop_on_eos[i] = not sp.ignore_eos
            nblocks[i] = len(r.seq.blocks)
        rids = [r.request_id for r in batch]
        return cls(
            rids=rids,
            row_of={rid: i for i, rid in enumerate(rids)},
            B=B, B_pad=B_pad, bt_width=btw, stop_w=sw,
            sample_mode=sample_mode,
            tokens=jnp.asarray(a["tokens"]),
            positions=jnp.asarray(a["positions"]),
            context_lens=jnp.asarray(a["context_lens"]),
            done=jnp.zeros(B_pad, bool),
            starts=jnp.asarray(a["starts"]),
            temps=jnp.asarray(a["temps"]),
            top_ks=jnp.asarray(a["top_ks"]),
            top_ps=jnp.asarray(a["top_ps"]),
            keys=jnp.stack(keys),
            max_toks=jnp.asarray(a["max_toks"]),
            stop_ids=jnp.asarray(stop_ids),
            stop_on_eos=jnp.asarray(stop_on_eos),
            lora_ids=jnp.asarray(a["lora_ids"]),
            block_tables=jnp.asarray(a["bt"]),
            _bt_np=a["bt"],
            _nblocks=nblocks,
        )

    def adopt_carry(self, carry) -> None:
        """Swap in the device arrays a chunk returned (no host sync)."""
        (self.tokens, self.positions, self.context_lens,
         self.done, self.starts) = carry

    def refresh_block_tables(self, running: list) -> bool:
        """Fold newly-allocated blocks into the device table; uploads
        the (small) table only when a row actually changed. Returns
        False when a row outgrew the padded width (caller rebuilds)."""
        dirty = False
        for r in running:
            i = self.row_of.get(r.request_id)
            if i is None or r.seq is None:
                continue
            nb = len(r.seq.blocks)
            if nb != self._nblocks[i]:
                if nb > self.bt_width:
                    return False
                self._bt_np[i, :nb] = r.seq.blocks
                self._nblocks[i] = nb
                dirty = True
        if dirty:
            self.block_tables = jnp.asarray(self._bt_np)
        return True


# ---------------------------------------------------------------------------
# the masked, early-exiting decode chunk
# ---------------------------------------------------------------------------


def decode_chunk_masked(
    params,
    tokens: jax.Array,        # [B] current tokens (carry)
    positions: jax.Array,     # [B] absolute positions of `tokens` (carry)
    block_tables: jax.Array,  # [B, MB]
    context_lens: jax.Array,  # [B] INCLUDING the current token (carry)
    cache,
    temperatures: jax.Array,  # [B]
    top_ks: jax.Array,        # [B]
    top_ps: jax.Array,        # [B]
    keys: jax.Array,          # [B] STABLE per-request PRNG keys
    starts: jax.Array,        # [B] absolute output index of step 0's token
    max_toks: jax.Array,      # [B] max_tokens budget (absolute)
    done: jax.Array,          # [B] bool carry: row already finished
    stop_ids: jax.Array,      # [B, S] stop-token sets, -1 padded
    stop_on_eos: jax.Array,   # [B] bool: EOS finishes the row (~ignore_eos)
    config,
    *,
    n_steps: int,
    block_size: int,
    trash_slot: int,
    eos_id: int,
    attn_impl: str = "auto",
    sample_mode: str = "full",
    lora=None,
):
    """Decode up to ``n_steps`` tokens with the stop ladder IN-GRAPH.

    Returns ``(tokens [n_steps, B], logprobs [n_steps, B],
    n_emitted [B], steps_run scalar, carry, cache)`` where carry is the
    next chunk's ``(tokens, positions, context_lens, done, starts)``.

    Per-row semantics match the host ladder in
    ``LLMEngine._append_chunk`` exactly: a token is emitted, THEN the
    row goes done if it was EOS (unless ignored), in the stop set, hit
    max_tokens, or hit the model's max_seq wall. Done rows freeze —
    trash-slot KV writes, no position/context advance (the RoPE table
    is never indexed past max_seq), masked 0-token/0-logprob outputs,
    same PRNG fold (unused) — so a chunk dispatched before the host
    even knows who finished still computes the identical stream for
    live rows. ``lax.while_loop`` exits once every row (pads included)
    is done: the all-done early-out."""
    B = tokens.shape[0]
    rows = jnp.arange(B)
    done0 = done | (context_lens <= 0)  # pad rows are born done

    toks_buf = jnp.zeros((n_steps, B), jnp.int32)
    lps_buf = jnp.zeros((n_steps, B), jnp.float32)
    n_emit0 = jnp.zeros(B, jnp.int32)

    def cond(carry):
        s, _tok, _pos, _ctx, dn, _ne, _tb, _lb, _cache = carry
        return (s < n_steps) & ~jnp.all(dn)

    def body(carry):
        s, tok, pos, ctx, dn, ne, tb, lb, cache = carry
        active = ~dn
        # slot for the fed token straight from the block table; done and
        # pad rows write the trash page, never block 0
        slot = (
            block_tables[rows, pos // block_size] * block_size
            + pos % block_size
        )
        slot = jnp.where(active, slot, trash_slot)
        logits, cache = decode_step(
            params, tok, pos, slot, block_tables, ctx, cache, config,
            block_size=block_size, attn_impl=attn_impl, lora=lora,
        )
        # key = fold(request key, absolute output index): identical to
        # the sync path for every live row, chunk partitioning invariant
        step_keys = jax.vmap(jax.random.fold_in)(keys, starts + s)
        nxt, lp = sample_tokens(
            logits, temperatures, top_ks, top_ps, step_keys,
            mode=sample_mode, done=dn,
        )
        ne2 = ne + active.astype(jnp.int32)
        # stop ladder, same conditions/threshold as _append_chunk
        hit_stop = jnp.any(stop_ids == nxt[:, None], axis=-1)
        hit_eos = stop_on_eos & (nxt == eos_id)
        hit_len = (starts + ne2) >= max_toks
        hit_seq = (ctx + 1) >= config.max_seq
        dn2 = dn | (active & (hit_eos | hit_stop | hit_len | hit_seq))
        tb = tb.at[s].set(jnp.where(active, nxt, 0))
        lb = lb.at[s].set(jnp.where(active, lp, 0.0))
        # frozen once done: token/position/context stop advancing
        tok2 = jnp.where(active, nxt, tok)
        pos2 = jnp.where(active, pos + 1, pos)
        ctx2 = jnp.where(active, ctx + 1, ctx)
        return (s + 1, tok2, pos2, ctx2, dn2, ne2, tb, lb, cache)

    (steps_run, tok, pos, ctx, dn, n_emit, toks_buf, lps_buf, cache) = (
        jax.lax.while_loop(
            cond, body,
            (jnp.asarray(0, jnp.int32), tokens, positions, context_lens,
             done0, n_emit0, toks_buf, lps_buf, cache),
        )
    )
    carry = (tok, pos, ctx, dn, starts + n_emit)
    return toks_buf, lps_buf, n_emit, steps_run, carry, cache
