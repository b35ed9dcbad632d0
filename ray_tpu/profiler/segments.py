"""Segment registry + chained-probe ladder runner.

The measurement primitive generalizes benchmarks/chained_probe.py: a
segment is one rung of a *cumulative ladder* of jitted programs, each a
superset of the previous rung's work (embed -> +LN/residual ->
+attention -> +MLP -> +loss -> +backward -> +optimizer). Every rung is
timed with chained-probe semantics — K data-dependent iterations, ONE
host fence at the end — so the per-rung time is pure device time, and
segment attribution falls out of telescoping differences: the segments
sum to the final rung (the whole step) by construction, and the gap
between the ladder total and an independently measured real step is
reported honestly as residual.

Chaining: each rung's carry feeds the next iteration (the train ladder
injects a zero-valued function of the rung's result into the embedding
table; the decode ladder feeds sampled/derived tokens forward), so the
final fence cannot land before every iteration's compute has executed —
the same impossible-to-fake guarantee bench.py's timed_steps relies on.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ray_tpu.profiler.costs import SegmentCost

# -- registry ----------------------------------------------------------------

_BUILDERS: dict[str, Callable] = {}


def register_segments(name: str):
    """Register a segment-ladder builder under a step name (the registry
    the benchmarks and `bench.py --profile` resolve builders through)."""

    def deco(fn):
        _BUILDERS[name] = fn
        return fn

    return deco


def segment_builders() -> dict[str, Callable]:
    return dict(_BUILDERS)


# -- primitives --------------------------------------------------------------


@dataclasses.dataclass
class FnPart:
    """One rung: ``fn(carry) -> carry`` closing over everything else.

    ``make_carry`` builds a fresh carry per run so donated rungs never
    invalidate a buffer another rung still references.
    """

    name: str
    fn: Callable
    make_carry: Callable[[], Any]
    donate: bool = False
    prejitted: bool = False  # fn already dispatches a compiled program
    in_step: bool = True     # counts toward the whole-step sum


@dataclasses.dataclass
class SegmentTiming:
    name: str
    ms: float                 # attributed time (ladder diff, clamped >= 0)
    cum_ms: float             # this rung's absolute per-iteration time
    cost: SegmentCost = dataclasses.field(default_factory=SegmentCost)
    in_step: bool = True


def _fence(tree) -> float:
    """Pull one element of the first leaf to the host: the transfer is
    data-dependent on the chain, so it cannot complete early."""
    leaf = jax.tree.leaves(tree)[0]
    return float(jnp.asarray(leaf).ravel()[0])


def _token(x: jax.Array) -> jax.Array:
    """Scalar f32 summary of a tensor; consuming it keeps the producing
    computation alive against DCE."""
    return jnp.sum(x.astype(jnp.float32))


def _effective_donate(want: bool) -> bool:
    # CPU XLA can't alias donated buffers; requesting it just prints a
    # warning per compile. Only donate where it actually goes in-place.
    return want and jax.devices()[0].platform == "tpu"


def chained_seconds(
    fn: Callable,
    make_carry: Callable[[], Any],
    *,
    iters: int = 8,
    warmup: int = 2,
    repeats: int = 3,
    donate: bool = False,
    prejitted: bool = False,
    fence_each: bool = False,
) -> float:
    """Per-iteration seconds of ``fn``: best of ``repeats`` timing loops
    of ``iters`` chained calls each (min-of-means rejects transient host
    contention, the dominant noise source on a shared CPU).

    ``fence_each=True`` fences every iteration instead (the host-sync
    cost probe: the difference vs the chained run is the round-trip the
    serving loop pays per step when it syncs each token).
    """
    jfn = fn if prejitted else jax.jit(
        fn, donate_argnums=(0,) if _effective_donate(donate) else ()
    )
    return _timed(jfn, make_carry, iters=iters, warmup=warmup,
                  repeats=repeats, fence_each=fence_each)


def _timed(jfn, make_carry, *, iters, warmup, repeats, fence_each=False) -> float:
    carry = make_carry()
    for _ in range(max(1, warmup)):
        carry = jfn(carry)
    _fence(carry)
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        for _ in range(iters):
            carry = jfn(carry)
            if fence_each:
                _fence(carry)
        if not fence_each:
            _fence(carry)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _timed_pipelined(jfn, make_carry, *, iters, warmup, repeats) -> float:
    """Per-iteration seconds with DOUBLE-BUFFERED fencing: dispatch
    iteration N+1 before fencing iteration N's result, so the host
    round-trip overlaps device compute (JAX async dispatch) — the
    measurement model of the engine's pipelined decode path. ``jfn``
    must not donate its carry (the lag-1 fence still reads it)."""
    carry = make_carry()
    for _ in range(max(1, warmup)):
        carry = jfn(carry)
    _fence(carry)
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        prev = jfn(carry)
        for _ in range(iters - 1):
            cur = jfn(prev)
            _fence(prev)  # overlaps cur's device work
            prev = cur
        _fence(prev)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def profile_segments(
    fn_parts: list[FnPart],
    *,
    iters: int = 8,
    warmup: int = 2,
    repeats: int = 2,
    passes: int = 2,
    with_costs: bool = True,
) -> list[SegmentTiming]:
    """Time a cumulative ladder; attribute each rung the difference vs
    the rung before it (independent parts — ``in_step=False`` — get
    their absolute time). Costs telescope the same way, from XLA's
    cost_analysis of each rung's compiled program.

    Timing sweeps the whole ladder ``passes`` times and keeps each
    rung's minimum: a host-contention spike long enough to cover one
    rung's repeats then lands on a DIFFERENT rung next pass instead of
    permanently inflating the same diff.

    Each rung is lowered + compiled ONCE; the timing loop calls the
    compiled executable and the cost model reads cost_analysis() off the
    same object (a second jit would double compile wall time)."""
    from ray_tpu.profiler.costs import cost_from_compiled

    jfns: list = []
    part_costs: list[SegmentCost] = []
    for part in fn_parts:
        if part.prejitted:
            jfns.append(part.fn)
            part_costs.append(SegmentCost())
            continue
        jfn = jax.jit(
            part.fn,
            donate_argnums=(0,) if _effective_donate(part.donate) else (),
        )
        try:
            exe = jfn.lower(part.make_carry()).compile()
            jfns.append(exe)
            part_costs.append(
                cost_from_compiled(exe) if with_costs else SegmentCost()
            )
        except Exception:  # noqa: BLE001 — fall back to plain jit dispatch
            jfns.append(jfn)
            part_costs.append(SegmentCost())

    best_ms: list[float] = [float("inf")] * len(fn_parts)
    for _ in range(max(1, passes)):
        for i, part in enumerate(fn_parts):
            sec = _timed(
                jfns[i], part.make_carry, iters=iters, warmup=warmup,
                repeats=repeats,
            )
            best_ms[i] = min(best_ms[i], sec * 1e3)

    out: list[SegmentTiming] = []
    prev_ms = 0.0
    prev_cost = SegmentCost(populated=True)
    for part, cum_ms, cost in zip(fn_parts, best_ms, part_costs):
        if part.in_step:
            seg = SegmentTiming(
                name=part.name,
                ms=max(0.0, cum_ms - prev_ms),
                cum_ms=cum_ms,
                cost=cost.minus(prev_cost) if cost.populated else cost,
                in_step=True,
            )
            prev_ms, prev_cost = cum_ms, (cost if cost.populated else prev_cost)
        else:
            seg = SegmentTiming(
                name=part.name, ms=cum_ms, cum_ms=cum_ms, cost=cost,
                in_step=False,
            )
        out.append(seg)
    return out


# -- generic train-step ladder (any loss_fn) ---------------------------------


def _inject_first_leaf(tree, tok: jax.Array):
    """Chain link for arbitrary pytrees: fold a zero-valued function of
    the rung's result into element 0 of the first leaf."""
    leaves, treedef = jax.tree.flatten(tree)
    l0 = leaves[0]
    leaves[0] = l0.at[(0,) * l0.ndim].add((tok * 0).astype(l0.dtype))
    return jax.tree.unflatten(treedef, leaves)


def generic_train_segments(
    loss_fn: Callable,
    optimizer,
    state,
    batch,
    *,
    step_body: Optional[Callable] = None,
    iters: int = 6,
    warmup: int = 2,
) -> tuple[list[FnPart], Callable]:
    """Coarse model-agnostic ladder for any ``make_train_step`` program:
    forward -> +backward -> +optimizer-update. ``loss_fn(params, batch)``
    returns a scalar or (loss, weight); ``step_body`` (the un-jitted
    step, when available) is used as the final rung so the ladder total
    telescopes to the real program."""
    import optax

    def scalar_loss(p):
        out = loss_fn(p, batch)
        return out[0] if isinstance(out, (tuple, list)) else out

    def mk_params():
        return jax.tree.map(jnp.copy, state.params)

    def mk_state():
        return jax.tree.map(jnp.copy, state)

    def fwd(p):
        return _inject_first_leaf(p, scalar_loss(p))

    def bwd(p):
        loss, grads = jax.value_and_grad(scalar_loss)(p)
        return _inject_first_leaf(p, loss + optax.global_norm(grads))

    if step_body is not None:
        def full(st):
            new_state, _ = step_body(st, batch)
            return new_state
    else:
        def full(st):
            loss, grads = jax.value_and_grad(scalar_loss)(st.params)
            updates, opt_state = optimizer.update(grads, st.opt_state, st.params)
            params = optax.apply_updates(st.params, updates)
            return dataclasses.replace(
                st, params=_inject_first_leaf(params, loss),
                opt_state=opt_state, step=st.step + 1,
            )

    parts = [
        FnPart("forward", fwd, mk_params),
        FnPart("backward", bwd, mk_params),
        FnPart("optimizer_update", full, mk_state, donate=True),
    ]

    def whole_fn(*, iters_=iters, warmup_=warmup, repeats_=3) -> float:
        return 1e3 * chained_seconds(
            full, mk_state, iters=iters_, warmup=warmup_, repeats=repeats_,
            donate=True,
        )

    return parts, whole_fn


# -- llama train-step ladder -------------------------------------------------


def _inject(params: dict, tok: jax.Array) -> dict:
    """Chain link: fold a zero-valued function of this iteration's result
    into the embedding row every rung reads first."""
    emb = params["embed"]
    return {**params, "embed": emb.at[0, 0].add((tok * 0).astype(emb.dtype))}


@register_segments("train_step")
def train_step_segments(
    config,
    params,
    batch: dict,
    optimizer,
    *,
    iters: int = 6,
    warmup: int = 2,
) -> tuple[list[FnPart], Callable]:
    """Ladder for one llama train step. Returns (parts, whole_fn) where
    ``whole_fn()`` measures the REAL jitted train step (train.step.
    make_train_step) with the same chained runner — the reference the
    ladder's telescoped total is checked against."""
    import optax

    from ray_tpu.models import llama
    from ray_tpu.nn.layers import (
        apply_rope,
        fused_cross_entropy_loss,
        rms_norm,
        rope_frequencies,
        swiglu,
    )
    from ray_tpu.ops.attention import attention
    from ray_tpu.train.step import TrainState, make_train_step

    c = config
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)

    def mk_params():
        # a REAL copy: the real-step reference and the optimizer rung
        # donate their carries, and a donated buffer shared with the
        # caller's params would poison every later rung (and the caller)
        return jax.tree.map(jnp.copy, params)

    def l0_embed(p):
        h = p["embed"].astype(c.dtype)[tokens]
        return _inject(p, _token(h))

    def _ln_block(h, lp, with_attn: bool):
        x = rms_norm(h, lp["ln1"], c.rms_eps)
        if with_attn:
            hd = c.head_dim
            q = jnp.einsum("bsd,dh->bsh", x, lp["wq"].astype(x.dtype)).reshape(
                B, S, c.n_heads, hd
            )
            k = jnp.einsum("bsd,dh->bsh", x, lp["wk"].astype(x.dtype)).reshape(
                B, S, c.n_kv_heads, hd
            )
            v = jnp.einsum("bsd,dh->bsh", x, lp["wv"].astype(x.dtype)).reshape(
                B, S, c.n_kv_heads, hd
            )
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            o = attention(q, k, v, causal=True, impl=c.attention_impl)
            o = jnp.einsum(
                "bsh,hd->bsd",
                o.reshape(B, S, c.n_heads * hd),
                lp["wo"].astype(x.dtype),
            )
            h = h + o
        else:
            # keep the norm alive without attention: a zero-free epsilon
            # mix (0 * x would let XLA fold the whole norm away)
            h = h + x * jnp.asarray(1e-6, x.dtype)
        x2 = rms_norm(h, lp["ln2"], c.rms_eps)
        return h + x2 * jnp.asarray(1e-6, x2.dtype)

    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    def l1_ln_residual(p):
        h = p["embed"].astype(c.dtype)[tokens]
        h, _ = jax.lax.scan(
            lambda h, lp: (_ln_block(h, lp, with_attn=False), None),
            h, p["layers"],
        )
        h = rms_norm(h, p["final_norm"], c.rms_eps)
        return _inject(p, _token(h))

    def l2_attention(p):
        h = p["embed"].astype(c.dtype)[tokens]
        h, _ = jax.lax.scan(
            lambda h, lp: (_ln_block(h, lp, with_attn=True), None),
            h, p["layers"],
        )
        h = rms_norm(h, p["final_norm"], c.rms_eps)
        return _inject(p, _token(h))

    def l3_mlp(p):
        h = llama.hidden_states(p, tokens, c)
        return _inject(p, _token(h))

    def l4_loss(p):
        loss, _ = llama.loss_and_weight_fn(p, batch, c)
        return _inject(p, loss)

    def loss_for_grad(p):
        return llama.loss_and_weight_fn(p, batch, c)

    # -- backward split: three cumulative grad rungs -------------------------
    # stop_gradient changes d/dp and never the primal, so every rung below
    # runs the identical forward and each rung's backward is a strict
    # superset of the previous one's. Telescoping then prices ce_bwd
    # (lm-head + fused-CE backward), +mlp_bwd (MLP/norm/residual/embed
    # backward), +attention_bwd (qkv/rope/attention/wo backward — the rest).
    seg_ids = batch.get("segment_ids")
    bwd_positions = llama.packed_positions(seg_ids, S)

    def _scoped_loss(p, h):
        return fused_cross_entropy_loss(
            h, llama.output_weight(p), batch["targets"], batch.get("mask")
        )

    def _grad_rung(scoped_loss):
        def rung(p):
            (loss, _), grads = jax.value_and_grad(scoped_loss, has_aux=True)(p)
            # global_norm consumes every grad leaf (keeps the scoped
            # backward alive) and is work the real step does too
            return _inject(p, loss + optax.global_norm(grads))
        return rung

    def loss_ce_scope(p):
        # gradient reaches only the lm-head/CE (tied embedding included
        # via output_weight); the trunk forward still runs, detached
        h = jax.lax.stop_gradient(
            llama.hidden_states(p, tokens, c, segment_ids=seg_ids)
        )
        return _scoped_loss(p, h)

    def _block_mlp_scope(h, lp):
        # mirrors llama._block exactly (identical primal) with the
        # attention branch detached after the wo projection: gradient
        # reaches the MLP, ln2, residual spine and embedding — not
        # qkv/rope/attention/wo (those price into attention_bwd)
        x = rms_norm(h, lp["ln1"], c.rms_eps)
        hd = c.head_dim
        q = jnp.einsum("bsd,dh->bsh", x, lp["wq"].astype(x.dtype)).reshape(
            B, S, c.n_heads, hd
        )
        k = jnp.einsum("bsd,dh->bsh", x, lp["wk"].astype(x.dtype)).reshape(
            B, S, c.n_kv_heads, hd
        )
        v = jnp.einsum("bsd,dh->bsh", x, lp["wv"].astype(x.dtype)).reshape(
            B, S, c.n_kv_heads, hd
        )
        q = apply_rope(q, cos, sin, bwd_positions)
        k = apply_rope(k, cos, sin, bwd_positions)
        o = attention(
            q, k, v, causal=True, segment_ids=seg_ids, impl=c.attention_impl
        )
        o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        o = jnp.einsum(
            "bsh,hd->bsd", o.reshape(B, S, c.n_heads * hd),
            lp["wo"].astype(x.dtype),
        )
        h = h + jax.lax.stop_gradient(o)
        x2 = rms_norm(h, lp["ln2"], c.rms_eps)
        return h + swiglu(x2, lp["w_gate"], lp["w_up"], lp["w_down"])

    def loss_mlp_scope(p):
        h = p["embed"].astype(c.dtype)[tokens]
        blk = _block_mlp_scope
        if c.remat:
            # mirror hidden_states' remat wrapping so this rung prices the
            # same rematerialized backward the real step runs
            if c.remat_policy == "dots":
                blk = jax.checkpoint(
                    blk,
                    policy=jax.checkpoint_policies.save_from_both_policies(
                        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                        jax.checkpoint_policies.save_only_these_names(
                            "attn_out", "attn_lse"
                        ),
                    ),
                )
            else:
                blk = jax.checkpoint(blk)
        h, _ = jax.lax.scan(
            lambda carry, lp: (blk(carry, lp), None), h, p["layers"]
        )
        h = rms_norm(h, p["final_norm"], c.rms_eps)
        return _scoped_loss(p, h)

    l5a_ce_bwd = _grad_rung(loss_ce_scope)
    l5b_mlp_bwd = _grad_rung(loss_mlp_scope)
    l5c_attention_bwd = _grad_rung(loss_for_grad)

    def mk_state():
        return TrainState.create(mk_params(), optimizer)

    def l6_optimizer(state):
        (loss, _), grads = jax.value_and_grad(loss_for_grad, has_aux=True)(
            state.params
        )
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        grad_norm = optax.global_norm(grads)
        return TrainState(
            params=_inject(new_params, loss + grad_norm),
            opt_state=opt_state,
            step=state.step + 1,
        )

    parts = [
        FnPart("embed", l0_embed, mk_params),
        FnPart("ln_residual", l1_ln_residual, mk_params),
        FnPart("attention", l2_attention, mk_params),
        FnPart("mlp", l3_mlp, mk_params),
        FnPart("lm_head_loss", l4_loss, mk_params),
        FnPart("ce_bwd", l5a_ce_bwd, mk_params),
        FnPart("mlp_bwd", l5b_mlp_bwd, mk_params),
        FnPart("attention_bwd", l5c_attention_bwd, mk_params),
        FnPart("optimizer_update", l6_optimizer, mk_state, donate=True),
    ]

    real_step = make_train_step(
        lambda p, b: llama.loss_and_weight_fn(p, b, c), optimizer
    )

    def whole_fn(*, iters_=iters, warmup_=warmup, repeats_=3) -> float:
        """Per-step ms of the real jitted train step, chained."""
        return 1e3 * chained_seconds(
            lambda st: real_step(st, batch)[0], mk_state,
            iters=iters_, warmup=warmup_, repeats=repeats_, prejitted=True,
        )

    return parts, whole_fn


# -- allreduce-overlap probe -------------------------------------------------


def allreduce_overlap_segments(
    config,
    params,
    batch: dict,
    *,
    iters: int = 6,
    warmup: int = 2,
    repeats: int = 3,
) -> tuple[list[SegmentTiming], Optional[float]]:
    """Standalone probe: how much of the gradient all-reduce hides behind
    the backward pass it is scheduled with?

    Three chained measurements — t_bwd (backward alone), t_bwd_ar
    (backward + psum of every grad leaf over a ``dp`` mesh of all local
    devices, one program so XLA may overlap), t_ar (the psum alone on
    grad-shaped buffers). What the schedule failed to hide is
    ``exposed = max(0, t_bwd_ar - t_bwd)``; the overlap ratio is
    ``(t_ar - exposed) / t_ar``.

    Honesty: with one device (tier-1 CPU) the psum lowers to ~a copy and
    t_ar sits at the timing noise floor — the ratio is then reported as
    None, not a fabricated 1.0. The number only means something on a
    multi-chip mesh.

    Returns ``(segments, overlap_ratio)``: two ``in_step=False``
    SegmentTimings ("allreduce" = t_ar, "allreduce_exposed" = exposed)
    that never count toward step coverage.
    """
    import numpy as np
    import optax
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models import llama

    c = config
    devices = jax.devices()
    n_dev = len(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))

    def allreduce(grads):
        def body(g):
            # mean-allreduce, the DP gradient exchange: psum then scale
            return jax.tree.map(
                lambda x: jax.lax.psum(x, "dp") / n_dev, g
            )

        return jax.shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=P()
        )(grads)

    def mk_params():
        return jax.tree.map(jnp.copy, params)

    def loss_for_grad(p):
        return llama.loss_and_weight_fn(p, batch, c)

    def bwd(p):
        (loss, _), grads = jax.value_and_grad(loss_for_grad, has_aux=True)(p)
        return _inject(p, loss + optax.global_norm(grads))

    def bwd_ar(p):
        (loss, _), grads = jax.value_and_grad(loss_for_grad, has_aux=True)(p)
        grads = allreduce(grads)
        return _inject(p, loss + optax.global_norm(grads))

    def ar_only(g):
        # grads share params' pytree/shapes, so param buffers stand in;
        # chaining through the first leaf keeps every psum live
        g2 = allreduce(g)
        return _inject_first_leaf(g2, _token(jax.tree.leaves(g2)[0]))

    t_bwd = 1e3 * chained_seconds(
        bwd, mk_params, iters=iters, warmup=warmup, repeats=repeats
    )
    t_bwd_ar = 1e3 * chained_seconds(
        bwd_ar, mk_params, iters=iters, warmup=warmup, repeats=repeats
    )
    t_ar = 1e3 * chained_seconds(
        ar_only, mk_params, iters=iters, warmup=warmup, repeats=repeats
    )

    exposed = max(0.0, t_bwd_ar - t_bwd)
    # ~10us: below the chained-timer's resolving power the psum cost is
    # indistinguishable from noise and any ratio would be an invention;
    # likewise a single device has no communication to overlap — the
    # one-device psum prices the grad-scaling copy, not an exchange
    noise_floor_ms = 0.01
    if n_dev < 2 or t_ar <= noise_floor_ms:
        ratio: Optional[float] = None
    else:
        ratio = max(0.0, min(1.0, (t_ar - exposed) / t_ar))

    segments = [
        SegmentTiming(name="allreduce", ms=t_ar, cum_ms=t_ar, in_step=False),
        SegmentTiming(
            name="allreduce_exposed", ms=exposed, cum_ms=t_bwd_ar,
            in_step=False,
        ),
    ]
    return segments, ratio


# -- decode-step ladder ------------------------------------------------------


@register_segments("decode_step")
def decode_step_segments(
    config,
    params,
    *,
    batch_size: int = 4,
    context_len: int = 32,
    block_size: int = 16,
    attn_impl: str = "auto",
    sample_mode: str = "full",
    iters: int = 8,
    warmup: int = 2,
    include_prefill: bool = True,
) -> tuple[list[FnPart], Callable]:
    """Ladder for one decode step of the serving engine: embed ->
    +qkv/rope -> +KV-write -> +KV-read (paged attention) -> +out-proj/MLP
    -> +lm-head (decode matmul) -> +sampling. Returns (parts, sync_fn):
    ``sync_fn()`` measures the full rung with a PER-ITERATION host fence,
    whose delta vs the chained run is the host-sync segment."""
    from ray_tpu.llm.sampling import sample_tokens
    from ray_tpu.models.llama_decode import init_cache
    from ray_tpu.nn.layers import apply_rope, rms_norm, rope_frequencies, swiglu
    from ray_tpu.ops.paged_attention import paged_attention

    c = config
    B = batch_size
    ctx = min(context_len, c.max_seq - 1)
    blocks_per_seq = -(-(ctx + 1) // block_size)
    num_slots = B * blocks_per_seq * block_size

    block_tables = jnp.arange(B * blocks_per_seq, dtype=jnp.int32).reshape(
        B, blocks_per_seq
    )
    context_lens = jnp.full((B,), ctx + 1, jnp.int32)
    positions = jnp.full((B,), ctx, jnp.int32)
    pos2 = positions[:, None]
    slot_mapping = (
        block_tables[jnp.arange(B), positions // block_size] * block_size
        + positions % block_size
    )
    temps = jnp.ones((B,), jnp.float32)
    top_ks = jnp.full((B,), 8, jnp.int32)
    top_ps = jnp.full((B,), 0.9, jnp.float32)
    keys = jax.vmap(jax.random.key)(jnp.arange(B, dtype=jnp.uint32))
    cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    hd = c.head_dim
    # stop-mask probe constants (the pipelined chunk's in-graph stop
    # ladder, llm/pipeline.py): a 2-wide stop set, per-row budgets, an
    # all-live done mask — representative shapes, never actually firing
    sm_stop_ids = jnp.full((B, 2), -1, jnp.int32).at[:, 0].set(5)
    sm_max_toks = jnp.full((B,), 1 << 20, jnp.int32)
    sm_starts = jnp.zeros((B,), jnp.int32)
    sm_done = jnp.zeros((B,), bool)
    sm_stop_eos = jnp.ones((B,), bool)

    def _stop_mask_epilogue(nxt, lp_):
        """The per-step stop-ladder math the pipelined decode chunk
        runs in-graph: stop-set match + EOS + budget/wall folds + the
        emitted-count update + output masking."""
        hit = jnp.any(sm_stop_ids == nxt[:, None], axis=-1)
        dn = sm_done | hit | (sm_stop_eos & (nxt == 2))
        dn = dn | ((sm_starts + 1) >= sm_max_toks)
        dn = dn | (jnp.full((B,), ctx + 2, jnp.int32) >= c.max_seq)
        ne = (~dn).astype(jnp.int32)
        nxt = jnp.where(dn, 0, nxt)   # output masking
        lp_ = jnp.where(dn, 0.0, lp_)
        return nxt, lp_, _token(dn) + _token(ne)

    def mk_carry():
        cache = init_cache(c, num_slots, trash_slots=block_size)
        toks = (jnp.arange(B, dtype=jnp.int32) + 1) % c.vocab_size
        return (toks, cache)

    # rung order — each feature requires everything before it (the
    # variant body references locals like `q`/`o`/`logits` produced by
    # the earlier features, so a non-cumulative set would NameError at
    # trace time deep inside the scan)
    _ORDER = ("qkv", "write", "attn", "mlp", "head", "sample", "mask")

    def _variant(parts_on: frozenset):
        on = [f for f in _ORDER if f in parts_on]
        assert set(parts_on) <= set(_ORDER) and on == list(_ORDER[: len(on)]), (
            f"decode ladder features must be a cumulative prefix of "
            f"{_ORDER}, got {sorted(parts_on)}"
        )

        def fn(carry):
            toks, cache = carry
            h = params["embed"].astype(c.dtype)[toks][:, None]  # [B, 1, D]
            acc = _token(h)

            def layer_step(lcarry, xs):
                h, acc = lcarry
                lp, kc, vc = xs
                if "qkv" in parts_on:
                    x = rms_norm(h, lp["ln1"], c.rms_eps)
                    q = jnp.einsum(
                        "bsd,dh->bsh", x, lp["wq"].astype(x.dtype)
                    ).reshape(B, 1, c.n_heads, hd)
                    k = jnp.einsum(
                        "bsd,dh->bsh", x, lp["wk"].astype(x.dtype)
                    ).reshape(B, 1, c.n_kv_heads, hd)
                    v = jnp.einsum(
                        "bsd,dh->bsh", x, lp["wv"].astype(x.dtype)
                    ).reshape(B, 1, c.n_kv_heads, hd)
                    q = apply_rope(q, cos, sin, pos2)
                    k = apply_rope(k, cos, sin, pos2)
                    if "write" not in parts_on:
                        acc = acc + _token(k) + _token(v)
                if "write" in parts_on:
                    kc = kc.at[:, slot_mapping].set(
                        k[:, 0].swapaxes(0, 1).astype(kc.dtype)
                    )
                    vc = vc.at[:, slot_mapping].set(
                        v[:, 0].swapaxes(0, 1).astype(vc.dtype)
                    )
                if "attn" in parts_on:
                    o = paged_attention(
                        q[:, 0], kc, vc, block_tables, context_lens,
                        block_size=block_size, impl=attn_impl,
                    )[:, None]
                    if "mlp" not in parts_on:
                        acc = acc + _token(o)
                elif "qkv" in parts_on:
                    acc = acc + _token(q)
                if "mlp" in parts_on:
                    h = h + jnp.einsum(
                        "bsh,hd->bsd",
                        o.reshape(B, 1, c.n_heads * hd),
                        lp["wo"].astype(o.dtype),
                    )
                    x2 = rms_norm(h, lp["ln2"], c.rms_eps)
                    h = h + swiglu(x2, lp["w_gate"], lp["w_up"], lp["w_down"])
                return (h, acc), (kc, vc)

            (h, acc), (nk, nv) = jax.lax.scan(
                layer_step, (h, acc), (params["layers"], cache["k"], cache["v"])
            )
            new_cache = {"k": nk, "v": nv}
            if "head" in parts_on:
                hf = rms_norm(h[:, 0], params["final_norm"], c.rms_eps)
                w_out = params.get("lm_head", None)
                if w_out is None:
                    w_out = params["embed"].T
                logits = jnp.einsum(
                    "bd,dv->bv", hf, w_out.astype(c.dtype)
                ).astype(jnp.float32)
                acc = acc + _token(logits[:, 0])
            if "sample" in parts_on:
                step_keys = jax.vmap(jax.random.fold_in)(keys, toks)
                nxt, lp_ = sample_tokens(
                    logits, temps, top_ks, top_ps, step_keys, mode=sample_mode
                )
                acc = acc + _token(lp_)
                if "mask" in parts_on:
                    nxt, lp_, tok_m = _stop_mask_epilogue(nxt, lp_)
                    acc = acc + tok_m
            else:
                nxt = toks
            nxt = (nxt + (acc * 0).astype(jnp.int32)) % c.vocab_size
            return (nxt, new_cache)

        return fn

    ladder = [
        ("embed", frozenset()),
        ("qkv_rope", frozenset({"qkv"})),
        ("kv_write", frozenset({"qkv", "write"})),
        ("kv_read_attn", frozenset({"qkv", "write", "attn"})),
        ("block_mlp", frozenset({"qkv", "write", "attn", "mlp"})),
        ("lm_head", frozenset({"qkv", "write", "attn", "mlp", "head"})),
        ("sampling", frozenset({"qkv", "write", "attn", "mlp", "head", "sample"})),
        ("stop_mask", frozenset(_ORDER)),
    ]
    parts = [
        FnPart(name, _variant(on), mk_carry, donate=True)
        for name, on in ladder
    ]

    if include_prefill:
        from ray_tpu.models.llama_decode import prefill

        S_pf = min(max(16, 1 << (max(1, ctx - 1)).bit_length()), c.max_seq)
        pf_tokens = jnp.ones((B, S_pf), jnp.int32)
        pf_positions = jnp.tile(jnp.arange(S_pf, dtype=jnp.int32), (B, 1))
        pf_blocks = -(-S_pf // block_size)
        pf_bt = jnp.arange(B * pf_blocks, dtype=jnp.int32).reshape(B, pf_blocks)
        offs = jnp.arange(S_pf, dtype=jnp.int32)
        pf_slots = (
            pf_bt[:, offs // block_size] * block_size + offs % block_size
        )
        pf_lens = jnp.full((B,), S_pf, jnp.int32)

        def mk_pf_carry():
            return init_cache(c, B * pf_blocks * block_size,
                              trash_slots=block_size)

        def pf_fn(cache):
            logits, new_cache = prefill(
                params, pf_tokens, pf_positions, pf_lens, pf_slots, pf_bt,
                pf_lens, cache, c, block_size=block_size,
            )
            k = new_cache["k"]
            return {
                **new_cache,
                "k": k.at[0, 0, 0, 0].add((_token(logits) * 0).astype(k.dtype)),
            }

        parts.append(
            FnPart(f"prefill_s{S_pf}", pf_fn, mk_pf_carry, donate=True,
                   in_step=False)
        )

    # mixed ragged probes (r24, in_step=False like the prefill probe):
    # a packed batch where half the rows serve a Qm-token prefill chunk
    # and half decode — the ONE-dispatch mixed step the mixed_batch
    # engine runs. `ragged_attention` prices the kernel alone;
    # `mixed_step` the full packed program (llama_decode.mixed_step).
    Qm = min(16, max(2, ctx))
    _q_lens = [Qm if i < (B + 1) // 2 else 1 for i in range(B)]
    Tm = sum(_q_lens)
    _cu = [0]
    for ql in _q_lens:
        _cu.append(_cu[-1] + ql)
    mx_cu = jnp.asarray(_cu, jnp.int32)
    _pos = []
    for ql in _q_lens:
        _pos.extend(range(ctx + 1 - ql, ctx + 1))
    mx_positions = jnp.asarray(_pos, jnp.int32)
    _row = []
    for i, ql in enumerate(_q_lens):
        _row.extend([i] * ql)
    _row = jnp.asarray(_row, jnp.int32)
    mx_slots = (
        block_tables[_row, mx_positions // block_size] * block_size
        + mx_positions % block_size
    )
    mx_tokens = jnp.ones((Tm,), jnp.int32)
    mx_q = jax.random.normal(
        jax.random.key(7), (Tm, c.n_heads, hd), c.dtype
    )

    def mk_mx_carry():
        return init_cache(c, num_slots, trash_slots=block_size)

    def ra_fn(cache):
        from ray_tpu.ops.ragged import ragged_attention

        o = ragged_attention(
            mx_q, cache["k"][0], cache["v"][0], block_tables, mx_cu,
            context_lens, block_size=block_size, max_q_len=Qm,
            impl=attn_impl,
        )
        k = cache["k"]
        return {
            **cache,
            "k": k.at[0, 0, 0, 0].add((_token(o) * 0).astype(k.dtype)),
        }

    def mx_fn(cache):
        from ray_tpu.models.llama_decode import mixed_step

        logits, new_cache = mixed_step(
            params, mx_tokens, mx_positions, mx_slots, block_tables,
            mx_cu, context_lens, cache, c, block_size=block_size,
            max_q_len=Qm, attn_impl=attn_impl,
        )
        k = new_cache["k"]
        return {
            **new_cache,
            "k": k.at[0, 0, 0, 0].add((_token(logits) * 0).astype(k.dtype)),
        }

    parts.append(
        FnPart("ragged_attention", ra_fn, mk_mx_carry, donate=True,
               in_step=False)
    )
    parts.append(
        FnPart("mixed_step", mx_fn, mk_mx_carry, donate=True,
               in_step=False)
    )

    def real_step(carry):
        """The REFERENCE program: llama_decode.decode_step + the jitted
        sampler + the pipelined stop-mask epilogue — the same per-step
        composition LLMEngine dispatches per decode round trip.
        Independent of the ladder's reconstruction, so coverage
        actually measures ladder fidelity."""
        from ray_tpu.models.llama_decode import decode_step

        toks, cache = carry
        logits, new_cache = decode_step(
            params, toks, positions, slot_mapping, block_tables,
            context_lens, cache, c, block_size=block_size,
            attn_impl=attn_impl,
        )
        step_keys = jax.vmap(jax.random.fold_in)(keys, toks)
        nxt, lp_ = sample_tokens(
            logits, temps, top_ks, top_ps, step_keys, mode=sample_mode
        )
        nxt, lp_, tok_m = _stop_mask_epilogue(nxt, lp_)
        nxt = (nxt + ((_token(lp_) + tok_m) * 0).astype(jnp.int32)) % c.vocab_size
        return (nxt, new_cache)

    def whole_fn(*, iters_=iters, warmup_=warmup, repeats_=3):
        """(chained_ms, synced_ms, pipelined_ms) of the real decode-step
        program: chained = pure device step; synced = a host fence every
        iteration (what one-token-per-sync serving pays); pipelined =
        double-buffered fencing (dispatch step N+1, THEN fence step N —
        what the async pipelined engine pays). synced - chained is the
        host_sync segment; synced - pipelined is the host_overlap
        saving the r16 pipelined path recovers."""
        jfn = jax.jit(
            real_step,
            donate_argnums=(0,) if _effective_donate(True) else (),
        )
        chained = _timed(jfn, mk_carry, iters=iters_, warmup=warmup_,
                         repeats=repeats_)
        synced = _timed(jfn, mk_carry, iters=iters_, warmup=warmup_,
                        repeats=repeats_, fence_each=True)
        # the overlap probe must NOT donate: the lag-1 fence reads a
        # carry the next dispatch has already consumed
        jfn_nd = jax.jit(real_step)
        pipelined = _timed_pipelined(jfn_nd, mk_carry, iters=iters_,
                                     warmup=warmup_, repeats=repeats_)
        return chained * 1e3, synced * 1e3, pipelined * 1e3

    return parts, whole_fn


# -- speculative-decode ladder ------------------------------------------------


@register_segments("spec_decode_step")
def spec_decode_segments(
    config,
    params,
    spec,
    *,
    batch_size: int = 4,
    context_len: int = 32,
    block_size: int = 16,
    iters: int = 6,
    warmup: int = 2,
) -> tuple[list[FnPart], Callable]:
    """Ladder for one SPECULATIVE decode round of the serving engine:
    draft (host n-gram lookup) -> +verify (one batched k+1-token pass
    through the paged prefill path) -> +accept (distribution-preserving
    acceptance/rejection) -> +kv_rollback (host block truncate/refill).

    Rungs mix host and device work, so every part is ``prejitted`` (the
    device pieces are jitted inside; cost-model fields stay empty and the
    segments classify unknown-bound — coverage is still measured against
    an independently-timed straight-line composition, which is the
    honesty property the regression gate guards). Histories are
    periodic, so the prompt-lookup drafter proposes a full k every round
    and the verify/accept rungs exercise their real shapes."""
    from ray_tpu.llm.kv_cache import BlockAllocator, SequenceBlocks
    from ray_tpu.llm.spec.accept import accept_draft
    from ray_tpu.models.llama_decode import init_cache, verify_tokens

    import numpy as np

    c = config
    B = batch_size
    k = spec.num_draft_tokens
    K1 = k + 1
    ctx = min(context_len, c.max_seq - K1 - 1)
    blocks_per_seq = -(-(ctx + K1 + 1) // block_size)
    num_blocks = B * blocks_per_seq + B  # headroom for the rollback churn
    num_slots = num_blocks * block_size

    # the CONFIGURED drafter, not a hardcoded lookup: with
    # method='draft_model' the draft rung must time the draft model's
    # prefill+decode (the dominant drafting cost), or the report would
    # attribute the wrong mechanism while meta claims spec_method
    drafter = spec.build_drafter(c)
    rng = np.random.default_rng(0)
    histories = []
    for _ in range(B):
        pat = rng.integers(3, c.vocab_size - 1, size=4).tolist()
        histories.append((pat * (ctx // 4 + 1))[:ctx])

    allocator = BlockAllocator(num_blocks, block_size)
    seqs = []
    for _ in range(B):
        s = SequenceBlocks(allocator)
        s.ensure_capacity(ctx + K1)
        s.num_tokens = ctx
        seqs.append(s)
    bt_w = max(len(s.blocks) for s in seqs)
    bt = np.zeros((B, bt_w), np.int32)
    for i, s in enumerate(seqs):
        bt[i, : len(s.blocks)] = s.blocks
    bt = jnp.asarray(bt)
    cache = init_cache(c, num_slots, trash_slots=block_size)

    # knobs with filtering active, matching the decode ladder's sampler
    # probe — mode "sample" then measures the exact-filter accept path
    # (the engine derives the cheaper categorical/greedy modes itself)
    temps = jnp.ones((B,), jnp.float32)
    top_ks = jnp.full((B,), 8, jnp.int32)
    top_ps = jnp.full((B,), 0.9, jnp.float32)
    keys = jax.vmap(jax.random.key)(jnp.arange(B, dtype=jnp.uint32))

    jverify = jax.jit(
        lambda t, p, sm, cl, acc: verify_tokens(
            params, t + (acc * 0).astype(jnp.int32), p, sm, bt, cl, cache,
            c, block_size=block_size,
        )[0]
    )

    def _draft():
        return [drafter.propose(str(i), histories[i], k) for i in range(B)]

    def _build(drafts):
        tokens = np.zeros((B, K1), np.int32)
        positions = np.zeros((B, K1), np.int32)
        slots = np.full((B, K1), num_slots, np.int32)
        ctx_lens = np.zeros(B, np.int32)
        d_toks = np.zeros((B, k), np.int32)
        d_lens = np.zeros(B, np.int32)
        for i, d in enumerate(drafts):
            row = [histories[i][-1]] + d
            tokens[i, : len(row)] = row
            positions[i, : len(row)] = np.arange(ctx - 1, ctx - 1 + len(row))
            for j in range(len(row)):
                slots[i, j] = seqs[i].slot(ctx - 1 + j)
            ctx_lens[i] = ctx + len(d)
            d_toks[i, : len(d)] = d
            d_lens[i] = len(d)
        return tokens, positions, slots, ctx_lens, d_toks, d_lens

    def r_draft(acc):
        drafts = _draft()
        return acc + 0.0 * float(len(drafts[0]))

    def r_verify(acc):
        t, p, sm, cl, _, _ = _build(_draft())
        logits = jverify(jnp.asarray(t), jnp.asarray(p), jnp.asarray(sm),
                         jnp.asarray(cl), acc)
        return _token(logits) * 1e-30

    def r_accept(acc):
        t, p, sm, cl, dt, dl = _build(_draft())
        logits = jverify(jnp.asarray(t), jnp.asarray(p), jnp.asarray(sm),
                         jnp.asarray(cl), acc)
        out, lp, a = accept_draft(
            logits, jnp.asarray(dt), jnp.asarray(dl), temps, top_ks, top_ps,
            keys, mode="sample",
        )
        # chain on tokens+accepts only: lp legitimately contains -inf for
        # zero-probability pad columns and would NaN the chain token
        return _token(a) * 1e-30 + _token(out) * 0.0

    def r_rollback(acc):
        t, p, sm, cl, dt, dl = _build(_draft())
        logits = jverify(jnp.asarray(t), jnp.asarray(p), jnp.asarray(sm),
                         jnp.asarray(cl), acc)
        out, lp, a = accept_draft(
            logits, jnp.asarray(dt), jnp.asarray(dl), temps, top_ks, top_ps,
            keys, mode="sample",
        )
        a_host = [int(x) for x in jnp.asarray(a)]
        for i, s in enumerate(seqs):
            s.num_tokens = ctx + int(dl[i])
            s.truncate_to(ctx + a_host[i])
            s.ensure_capacity(ctx + K1)
            s.num_tokens = ctx
        return _token(a) * 1e-30

    def mk_carry():
        return jnp.zeros((), jnp.float32)

    parts = [
        FnPart("draft", r_draft, mk_carry, prejitted=True),
        FnPart("verify", r_verify, mk_carry, prejitted=True),
        FnPart("accept", r_accept, mk_carry, prejitted=True),
        FnPart("kv_rollback", r_rollback, mk_carry, prejitted=True),
    ]

    def whole_fn(*, iters_=iters, warmup_=warmup, repeats_=3) -> float:
        """Per-round ms of the straight-line draft->verify->accept->
        rollback composition (independent of the ladder variants)."""
        return 1e3 * chained_seconds(
            r_rollback, mk_carry, iters=iters_, warmup=warmup_,
            repeats=repeats_, prejitted=True,
        )

    return parts, whole_fn
