"""Tensor-parallel matmuls whose transfers run beside the MXU.

Under a mesh with ``tp > 1`` the plain layout replicates the residual
stream over ``tp``: every row-parallel matmul (``wo``, ``w_down``) ends
in an all-reduce that the next op waits for. Here the residual stream
stays sharded over ``tp`` along the tokens (Megatron sequence
parallelism on the ``tp`` axis) and each matmul is cut into ``tp`` token
blocks inside a ``jax.shard_map`` over ``tp`` alone (every other mesh
axis stays automatic, as ops/attention.py does for the flash kernel),
so that one block crosses the link by ``ppermute`` while the MXU works
on another:

  * ``ag_matmul``: all-gather then matmul, for column-parallel weights
    (``wq/wk/wv``, ``w_gate/w_up``): [B, S/tp, D] -> [B, S, N/tp], or
    the ring's own blocks [tp, B, S/tp, N/tp] for ``rs_matmul``;
  * ``rs_matmul``: matmul then reduce-scatter, for row-parallel weights:
    [B, S, F/tp] -> [B, S/tp, D], summed over ``tp`` in x's dtype and
    named ``tp_rs_out`` for a remat policy to save.

Each is the other's transpose, and AD turns one ring into the other.
The same bytes cross the link as with the all-reduce; they are no
longer waited for. Callers (models/llama.py ``_block``) come here only
under an ambient ``parallel_context`` with ``tp > 1``; a site the ring
cannot serve (ring attention's ``sp > 1``, inside the pipeline's
``shard_map``, a dimension ``tp`` does not divide) takes the plain
einsum, and the compiler its all-reduce.

The choice is made while tracing. Each traced site is a layer span:
``obs.layer_counters()`` counts ``tp_overlap.ag_matmul`` and
``tp_overlap.rs_matmul`` (overlapped) against ``tp_overlap.plain``; a
step program traces a block more than once (shapes, then derivatives).
The rings are the dense MLP's and the attention projections': an expert
configuration's FFN (models/moe.py) never comes here, its grouped
matmuls are the partitioner's to place under ``tp > 1``, and it counts
its own sites as ``moe.ffn``, not as ``tp_overlap.plain``.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu import obs
from ray_tpu.parallel.context import current_mesh, current_rules
from ray_tpu.parallel.sharding import constrain

_AXIS = "tp"

__all__ = ["ag_matmul", "rs_matmul"]


def _ring_size(tokens: int, sharded_dims: Sequence[int]) -> int:
    """`tp` when the ring can serve this site, else 1."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    tp = mesh.shape.get(_AXIS, 1)
    # inside another shard_map (the pipeline's, manual over `pp`) the
    # plain path: a second, partly automatic shard_map nested there
    # crashes XLA's partitioner (CPU backend, jaxlib 0.9.0)
    if (jax.sharding.get_abstract_mesh().manual_axes or mesh.shape.get("sp", 1) > 1
            or any(n % tp for n in (tokens, *sharded_dims))):
        return 1
    return tp


def _to_next_chip(tp: int) -> list[tuple[int, int]]:
    return [(j, (j + 1) % tp) for j in range(tp)]


def _shard_map(f, in_specs, out_specs):
    return jax.shard_map(f, mesh=current_mesh(), in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset({_AXIS}), check_vma=False)


def _by_batch(x: jax.Array) -> jax.Array:
    """Pin a token block [B, s, n] inside the ring to the batch layout on
    the axes that stay automatic. Left to itself the partitioner shards
    the blocks of the backward ring along the weights' fsdp dimension
    and pays for it with all-to-alls of activation size."""
    mesh, rules = current_mesh(), current_rules()
    if x.shape[0] % math.prod(mesh.shape[a] for a in jax.tree.leaves(rules["batch"])):
        return x  # a batch the data axes do not divide: the partitioner's to place
    return constrain(x, mesh, rules, ("batch", None, None))


def ag_matmul(x: jax.Array, ws: Sequence[jax.Array], *,
              token_order: bool = True) -> tuple[jax.Array, ...]:
    """``tuple(x @ w for w in ws)`` for column-parallel ``ws``.

    x [B, S, D] is sharded over ``tp`` along S, each w [D, N] along N;
    each result holds every token and is sharded along N. A chip
    multiplies the token block it holds while ``ppermute`` brings the
    next one round the ring.

    With ``token_order`` the results are [B, S, N]. Without, they are
    the ring's own blocks [tp, B, S/tp, N], block j holding the tokens
    chip ``me - j`` started with: a different order on every chip, free
    of any copy, for a consumer that treats all tokens alike and hands
    the blocks on to ``rs_matmul`` (the MLP)."""
    tp = _ring_size(x.shape[1], [w.shape[1] for w in ws])
    if tp == 1:
        with obs.layer_span("tp_overlap.plain"):
            outs = tuple(jnp.einsum("bsd,dn->bsn", x, w) for w in ws)
            return outs if token_order else tuple(o[None] for o in outs)
    perm = _to_next_chip(tp)

    def ring(blk, *ws):
        blocks = []  # [step][weight]
        for step in range(tp):
            blk = _by_batch(blk)
            # sent before the multiply that hides it
            nxt = jax.lax.ppermute(blk, _AXIS, perm) if step < tp - 1 else None
            # the barrier is for the backward ring: there the block's
            # gradient is these products plus what arrives over the
            # link, and fused into the last product that sum would make
            # the MXU wait for the link
            here = jax.lax.optimization_barrier(blk)
            blocks.append([_by_batch(jnp.einsum("bsd,dn->bsn", here, w)) for w in ws])
            blk = nxt
        outs = [jnp.stack(of_w) for of_w in zip(*blocks)]
        if not token_order:
            return tuple(outs)
        # after `step` hops the block in hand is chip me - step's, so
        # tokens of chip b are block me - b
        me = jax.lax.axis_index(_AXIS)
        return tuple(
            jnp.concatenate(
                [jax.lax.dynamic_index_in_dim(out, (me - b) % tp, 0, keepdims=False)
                 for b in range(tp)], axis=1)
            for out in outs)

    out_spec = P(None, None, _AXIS) if token_order else P(None, None, None, _AXIS)
    with obs.layer_span("tp_overlap.ag_matmul"), jax.named_scope("tp_overlap.ag_matmul"):
        return _shard_map(
            ring,
            in_specs=(P(None, _AXIS, None),) + (P(None, _AXIS),) * len(ws),
            out_specs=(out_spec,) * len(ws),
        )(x, *ws)


def rs_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` for a row-parallel ``w``, summed over ``tp``.

    x is sharded over ``tp`` along F and is either [B, S, F] or the
    blocks [tp, B, S/tp, F] of ``ag_matmul(token_order=False)``; w
    [F, D] is sharded along F; the result [B, S, D] is the sum of the
    chips' partial products, sharded along S. A chip first multiplies
    the token block furthest round the ring, and each partial sum
    travels one hop while the next block is multiplied; the last block
    is the chip's own. Partial sums add in x's dtype, as the all-reduce
    of the plain path does."""
    if x.ndim == 4 and x.shape[0] == 1:  # the blocks of a ring of one
        x = x[0]
    blocked = x.ndim == 4
    tp = _ring_size(x.shape[1] * x.shape[0] if blocked else x.shape[1], [w.shape[0]])
    if blocked and x.shape[0] != tp:
        raise ValueError(f"{x.shape[0]} token blocks for a ring of {tp}")
    if tp == 1:
        with obs.layer_span("tp_overlap.plain"):
            return jnp.einsum("bsf,fd->bsd", x, w)
    perm = _to_next_chip(tp)

    def ring(x, w):
        if blocked:  # block j is chip me - j's tokens; split transposes to a concatenate
            blocks = [b[0] for b in jax.lax.split(x, [1] * tp, axis=0)]
        else:
            me, rows = jax.lax.axis_index(_AXIS), x.shape[1] // tp
        acc = None
        for step in range(tp):
            # chip me + 1 adds its own product for the same tokens next
            # step, so these are chip me - step - 1's: block step + 1
            blk = blocks[(step + 1) % tp] if blocked else jax.lax.dynamic_slice_in_dim(
                x, ((me - step - 1) % tp) * rows, rows, axis=1)
            part = _by_batch(jnp.einsum("bsf,fd->bsd", _by_batch(blk), w))
            # the barrier keeps the sum out of the product's fusion: the
            # product must not wait for what is still on the link
            part = jax.lax.optimization_barrier(part)
            acc = part if acc is None else jax.lax.ppermute(acc, _AXIS, perm) + part
        # named so that a remat policy can save the sum: it is an add,
        # not a dot, and rebuilding it from the saved products would
        # send the partial sums round the ring a second time
        return jax.ad_checkpoint.checkpoint_name(acc, "tp_rs_out")

    with obs.layer_span("tp_overlap.rs_matmul"), jax.named_scope("tp_overlap.rs_matmul"):
        return _shard_map(
            ring,
            in_specs=(P(None, None, None, _AXIS) if blocked else P(None, None, _AXIS),
                      P(_AXIS, None)),
            out_specs=P(None, _AXIS, None),
        )(x, w)
