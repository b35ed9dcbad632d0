"""Pipeline parallelism: GPipe-style collective pipelining over the mesh
`pp` axis, inside ONE jitted SPMD program.

Reference analog: Ray's pipeline parallelism is delegated — vLLM drives
PP through compiled graphs (python/ray/dag/compiled_dag_node.py:795)
with NCCL channels between stage actors, configured by
pipeline_parallel_degree (llm/.../vllm/vllm_models.py:121). TPU-native
redesign: stages are shards of the `pp` mesh axis; inter-stage transfer
is `lax.ppermute` over ICI (the channel), and the microbatch schedule is
a `lax.scan` — the whole pipeline compiles to one XLA program, no
per-hop driver round-trips, and autodiff differentiates straight
through the schedule (GPipe: backward replays stages in reverse).

Schedule: classic GPipe fill-drain with rotating buffers. With S stages
and M = S microbatches the scan runs 2S - 1 ticks; at tick t, stage s
computes microbatch t - s (mod S, garbage outside the window — the
bubble). Microbatch inputs live SHARDED over pp (stage s starts holding
microbatch s) and rotate -1 each tick so stage 0 always finds the next
microbatch locally; retired outputs rotate -1 likewise so microbatch j
ends resident on stage j. Everything cross-stage is a ppermute — no
all-reduce anywhere in the forward OR backward path (the transpose of a
ppermute is the inverse ppermute), which keeps bf16 activations off
XLA-CPU's fragile all-reduce promotion pass and keeps TPU traffic to
neighbor hops on the ICI ring.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    h: jax.Array,
    n_micro: Optional[int] = None,
    axis: str = "pp",
) -> jax.Array:
    """Apply a stack of layers pipelined over the mesh `pp` axis.

    stage_fn(stage_params, x) applies ONE stage's layers (leading dim of
    stage_params = layers_per_stage) to activations x [mb, S, D].
    stacked_params: pytree with leading dim n_stages (sharded over pp).
    h: [B, S, D] full-batch activations entering the stack.

    Returns activations after all stages, [B, S, D] — numerically equal
    to applying the stages sequentially (GPipe semantics).
    """
    pp = mesh.shape[axis]
    if pp == 1:  # degenerate: no pipeline, just run the single stage
        return stage_fn(jax.tree.map(lambda x: x[0], stacked_params), h)
    M = int(n_micro) if n_micro else pp
    if M != pp:
        raise NotImplementedError(
            f"rotating-buffer schedule needs n_micro == pp (got {M} != {pp})"
        )
    B = h.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    hm = h.reshape((M, B // M) + h.shape[1:])
    fwd = [(i, (i + 1) % pp) for i in range(pp)]  # to the next stage
    bwd = [(i, (i - 1) % pp) for i in range(pp)]  # buffer rotation
    last = pp - 1

    def body(hm_local, stage_params):
        # manual over `pp` only: hm_local [1, mb, S, D] is THIS stage's
        # resident microbatch; stage_params this stage's layer slice
        stage_params = jax.tree.map(lambda x: x[0], stage_params)
        stage = jax.lax.axis_index(axis)
        inputs = hm_local[0]
        state = jnp.zeros_like(inputs)
        out_buf = jnp.zeros_like(inputs)

        def tick(carry, t):
            inputs, state, out_buf = carry
            # retired microbatches drift -1 so microbatch j lands on stage j
            out_buf = jax.lax.ppermute(out_buf, axis, bwd)
            x = jnp.where(stage == 0, inputs, state)
            y = stage_fn(stage_params, x)
            out_idx = t - last
            writing = (stage == last) & (out_idx >= 0) & (out_idx < M)
            out_buf = jnp.where(writing, y, out_buf)
            state = jax.lax.ppermute(y, axis, fwd)
            inputs = jax.lax.ppermute(inputs, axis, bwd)
            return (inputs, state, out_buf), None

        (inputs, state, out_buf), _ = jax.lax.scan(
            tick, (inputs, state, out_buf), jnp.arange(M + pp - 1)
        )
        return out_buf[None]  # [1, mb, S, D], sharded back over pp

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        axis_names=frozenset({axis}),
        check_vma=False,
    )(hm, stacked_params)
    return out.reshape(h.shape)


def stack_stages(layer_params: Any, n_stages: int) -> Any:
    """[L, ...] stacked layer params -> [n_stages, L/n_stages, ...]."""

    def split(x):
        L = x.shape[0]
        if L % n_stages != 0:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])

    return jax.tree.map(split, layer_params)
