"""Jitted train-step construction.

One compiled SPMD program per step: forward, backward, optimizer update,
all under a single `jax.jit` with donated state. Gradient reductions,
FSDP all-gathers/reduce-scatters, and TP collectives are inserted by XLA
from the shardings of the inputs — the framework never issues an
explicit allreduce on the training path (contrast reference:
python/ray/train/torch/config.py:115, which bootstraps a NCCL process
group that user code then drives).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu import obs
from ray_tpu.parallel.sharding import ShardingRules, constrain, tree_shardings


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, optimizer: optax.GradientTransformation) -> "TrainState":
        # Every param-shaped leaf of the optimizer state (Adam's moments)
        # is born where its param was placed. Nothing propagates by
        # itself: zeros_like under jit does not depend on its input, so
        # without out_shardings the whole state — twice the model — lands
        # unsharded on the default device. Params nobody placed
        # (uncommitted) leave their moments free to follow them later.
        # The scalars (step, Adam's count) are born replicated over the
        # params' mesh, which is how a step hands them back: born on the
        # default device they made the second call of a sharded step a
        # second trace, lowering and compile of the whole program.
        meshes = {
            p.sharding.mesh for p in jax.tree.leaves(params)
            if getattr(p, "committed", False) and isinstance(p.sharding, NamedSharding)
        }
        everywhere = NamedSharding(meshes.pop(), PartitionSpec()) if len(meshes) == 1 else None
        shardings = optax.tree_map_params(
            optimizer,
            lambda _, p: p.sharding if getattr(p, "committed", False) else None,
            jax.eval_shape(optimizer.init, params),
            params,
            transform_non_params=lambda _: everywhere,
        )
        opt_state = jax.jit(optimizer.init, out_shardings=shardings)(params)
        step = jnp.zeros((), jnp.int32)
        if everywhere is not None:
            step = jax.device_put(step, everywhere)
        return cls(params=params, opt_state=opt_state, step=step)


def init_sharded_params(
    init_fn: Callable[..., Any],
    logical_tree: Any,
    mesh,
    rules: ShardingRules,
    *args,
) -> Any:
    """Run a param initializer with outputs born sharded (no host round-trip).
    Layer span train.init_params covers the compile (or cache load) and
    the dispatch; the device may still be filling the arrays after it."""
    with obs.layer_span("train.init_params"):
        shardings = tree_shardings(mesh, rules, logical_tree)
        return jax.jit(init_fn, out_shardings=shardings)(*args)


# VMEM one operation of the compiled step may use, by the chip's kind. XLA's
# default is 16 MiB of the 128 a v5e core has, and it tiles its matmul fusions
# to fit: the head's weight gradient, fused with the optimizer's update into
# one operation of three [D, V] results, ran at 48-52% of the MXU's peak and
# the MLP's matmuls at 75-81. Not more than this: what no operation claims is
# where XLA keeps whole arrays, and from 40 MiB the expert layer's 96 MiB token
# table no longer fits beside it; under a mesh 64 MiB loses outright (PERF.md,
# PR 29, has the sweep). A kind that is not here keeps the compiler's default.
_SCOPED_VMEM_KIB = {"TPU v5 lite": 32 * 1024}


def _compiler_options(mesh) -> Optional[dict]:
    if jax.default_backend() != "tpu":
        return None
    device = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
    kib = _SCOPED_VMEM_KIB.get(device.device_kind)
    return None if kib is None else {"xla_tpu_scoped_vmem_limit_kib": kib}


def make_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh=None,
    rules: Optional[ShardingRules] = None,
    batch_axes: tuple = ("batch", "seq"),
    grad_accum: int = 1,
):
    """Build `step(state, batch) -> (state, metrics)` as one jitted program.

    loss_fn(params, batch) -> scalar loss, or (loss, weight) where weight is
    the number of valid tokens the mean was taken over, or (loss, weight,
    statistics): a small tree of arrays the model counted on the way (an
    expert layer's tokens per expert, models/moe.py), which the step
    returns as metrics["stats"] (stacked over the microbatches when
    grad_accum > 1). With grad_accum > 1,
    the batch's leading dim is split into microbatches folded through
    `lax.scan` (keeps the compiled program static; no data-dependent
    Python). Microbatch losses/grads are combined weighted by `weight`, so
    masked batches match the unaccumulated result; scalar-returning loss
    fns get uniform weights (exact only when every microbatch has the same
    number of valid tokens).

    A batch that is a dict reaches loss_fn with one more entry, `step`:
    how many times loss_fn was evaluated before (int32 scalar: the
    optimizer's step count, and under grad_accum that count x grad_accum +
    the microbatch's index, so no two microbatches are handed the same),
    which is what an objective that draws its own noise folds into its key
    (models/block_diffusion.py). An objective that does not read it
    compiles to the program it always was.
    """
    if mesh is not None and rules is None:
        from ray_tpu.parallel.sharding import default_rules

        rules = default_rules()

    def compute_grads(params, batch):
        """Returns (loss, weight, grads, statistics); weight=1 for scalar
        loss fns, statistics None where loss_fn returns none."""
        from ray_tpu.parallel.context import parallel_context

        if mesh is not None:
            # Ambient (mesh, rules) so mesh-aware ops inside the model —
            # ring attention on `sp`, expert all-to-all on `ep` — can build
            # their shard_maps without signature plumbing.
            with parallel_context(mesh, rules):
                return _compute_grads_inner(params, batch)
        return _compute_grads_inner(params, batch)

    def _compute_grads_inner(params, batch):
        def with_weight(params, batch):
            # one trace of the model serves both forms of loss_fn (asking
            # eval_shape first traced it twice: seconds of every start-up)
            out = loss_fn(params, batch)
            if not isinstance(out, (tuple, list)):
                return out, (jnp.ones((), jnp.float32), None)
            return out[0], (out[1], out[2] if len(out) > 2 else None)

        (loss, (weight, stats)), grads = jax.value_and_grad(
            with_weight, has_aux=True)(params, batch)
        return loss, weight, grads, stats

    def step(state: TrainState, batch):
        if mesh is not None:
            batch = jax.tree.map(
                lambda x: constrain(
                    x, mesh, rules, batch_axes[: x.ndim] + (None,) * (x.ndim - len(batch_axes))
                ),
                batch,
            )
        def counted(batch, index=None):
            if not isinstance(batch, dict):
                return batch
            return {**batch, "step": state.step if index is None
                    else state.step * grad_accum + index}

        if grad_accum == 1:
            loss, _, grads, stats = compute_grads(state.params, counted(batch))
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum) + x.shape[1:]),
                batch,
            )

            def accum(carry, indexed):
                loss_i, w, g, stats_i = compute_grads(state.params, counted(*indexed))
                acc_loss, acc_w, acc_g = carry
                new = (
                    acc_loss + loss_i * w,
                    acc_w + w,
                    jax.tree.map(lambda a, b: a + b * w, acc_g, g),
                )
                return new, stats_i

            zero = (
                jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params),
            )
            (loss_sum, w_sum, grad_sum), stats = jax.lax.scan(
                accum, zero, (micro, jnp.arange(grad_accum, dtype=state.step.dtype)))
            loss = loss_sum / w_sum
            with jax.named_scope("optim"):
                grads = jax.tree.map(lambda g: g / w_sum, grad_sum)

        with jax.named_scope("optim"):
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
        new_state = TrainState(params=params, opt_state=opt_state, step=state.step + 1)
        metrics = {"loss": loss, "grad_norm": grad_norm}
        if stats is not None:
            metrics["stats"] = stats
        return new_state, metrics

    # noted for obs.op_names(): which block of the model an
    # operation of the compiled step belongs to, asked after the fact
    return obs.note_program("train.step", jax.jit(
        step, donate_argnums=(0,), compiler_options=_compiler_options(mesh)))
