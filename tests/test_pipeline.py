"""Pipeline-parallel tests: the GPipe schedule over the mesh `pp` axis
must reproduce the sequential layer stack exactly — forward AND backward
(reference role: vLLM PP via compiled graphs, compiled_dag_node.py:795;
here it's ppermute + lax.scan inside one jitted program)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.context import parallel_context
from ray_tpu.parallel.mesh import MeshSpec, make_mesh
from ray_tpu.parallel.pipeline import pipeline_apply, stack_stages
from ray_tpu.parallel.sharding import default_rules

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def _batch(cfg, key=1, B=8, S=32):
    tok = jax.random.randint(jax.random.key(key), (B, S + 1), 0, cfg.vocab_size, jnp.int32)
    return {"tokens": tok[:, :-1], "targets": tok[:, 1:]}


def test_pipeline_apply_matches_sequential_mlp():
    """Raw pipeline_apply on a toy stacked MLP == sequential scan."""
    mesh = make_mesh(MeshSpec(pp=4, tp=2), devices=jax.devices()[:8])
    L, D = 8, 16
    ws = jax.random.normal(jax.random.key(0), (L, D, D), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.key(1), (8, 4, D), jnp.float32)

    def stage(stage_ws, h):
        def blk(carry, w):
            return jnp.tanh(carry @ w), None

        out, _ = jax.lax.scan(blk, h, stage_ws)
        return out

    ref, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)
    out = jax.jit(
        lambda w, h: pipeline_apply(mesh, stage, stack_stages(w, 4), h)
    )(ws, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_llama_pp2_loss_and_grads_match_pp1():
    cfg = llama.LLAMA_TINY  # 2 layers -> 2 stages
    params = llama.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    ref_loss = float(jax.jit(lambda p, b: llama.loss_fn(p, b, cfg))(params, batch))

    mesh = make_mesh(MeshSpec(pp=2, ep=2, tp=2), devices=jax.devices()[:8])
    rules = default_rules(layers="pp")

    def pl(p, b):
        with parallel_context(mesh, rules):
            return llama.loss_fn(p, b, cfg)

    pp_loss = float(jax.jit(pl)(params, batch))
    assert abs(pp_loss - ref_loss) < 2e-3, (pp_loss, ref_loss)

    g = jax.jit(jax.grad(pl))(params, batch)
    g_ref = jax.jit(jax.grad(lambda p, b: llama.loss_fn(p, b, cfg)))(params, batch)

    def norm(t):
        return float(
            jax.tree.reduce(
                lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))), t, 0.0
            )
        )

    assert norm(g) == pytest.approx(norm(g_ref), rel=1e-2)
    # per-leaf agreement (not just the aggregate)
    for path, leaf in jax.tree_util.tree_leaves_with_path(g):
        ref_leaf = {tuple(str(p) for p in kp): v
                    for kp, v in jax.tree_util.tree_leaves_with_path(g_ref)}[
            tuple(str(p) for p in path)
        ]
        np.testing.assert_allclose(
            np.asarray(leaf, np.float32), np.asarray(ref_leaf, np.float32),
            atol=5e-2, rtol=5e-2,
        )


def test_pipeline_batch_not_divisible_raises():
    mesh = make_mesh(MeshSpec(pp=4, tp=2), devices=jax.devices()[:8])
    ws = jnp.zeros((4, 8, 8))
    x = jnp.zeros((6, 2, 8))  # 6 % 4 != 0
    with pytest.raises(ValueError, match="not divisible"):
        pipeline_apply(mesh, lambda w, h: h, stack_stages(ws, 4), x)


def test_pipeline_training_reduces_loss():
    """A few pipelined train steps actually learn (end-to-end with optax)."""
    import optax

    from ray_tpu.train.step import TrainState, make_train_step

    cfg = llama.LLAMA_TINY
    mesh = make_mesh(MeshSpec(pp=2, ep=2, tp=2), devices=jax.devices()[:8])
    rules = default_rules(layers="pp")
    params = llama.init_params(cfg, jax.random.key(0))
    opt = optax.adamw(1e-2)
    state = TrainState.create(params, opt)
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh=mesh, rules=rules
    )
    batch = _batch(cfg)
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] / 1.5, losses


@pytest.mark.parametrize(
    "spec", [MeshSpec(pp=2, fsdp=2), MeshSpec(pp=2)], ids=["pp_fsdp", "pp_only"]
)
def test_pipeline_train_step_with_flash_matches_xla(spec):
    """`attention(impl="flash")` shards its kernel over the mesh itself,
    and under pp it is already inside the pipeline's shard_map: it takes
    over only the axes that are still automatic (or none)."""
    import dataclasses

    import optax

    from ray_tpu.train.step import TrainState, make_train_step

    mesh = make_mesh(spec, devices=jax.devices()[: math.prod(spec.sizes())])
    rules = default_rules(layers="pp")
    opt = optax.adamw(1e-2)
    batch = _batch(llama.LLAMA_TINY)
    losses = {}
    for impl in ("xla", "flash"):
        cfg = dataclasses.replace(llama.LLAMA_TINY, attention_impl=impl)
        state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
        step = make_train_step(
            lambda p, b, cfg=cfg: llama.loss_fn(p, b, cfg), opt, mesh=mesh, rules=rules
        )
        losses[impl] = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses[impl].append(float(metrics["loss"]))
    np.testing.assert_allclose(losses["flash"], losses["xla"], rtol=5e-3)
    assert losses["flash"][-1] < losses["flash"][0]
