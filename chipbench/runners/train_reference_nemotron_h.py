"""The runner of a Nemotron-H cell: runners/train_reference_from_config.py
(the selection bias BALANCED between the init and the first step, the
first loss within `check.loss_tol`, the first step's routing within
`check.routing_tol`, no dropped pair and every pair counted in every
step), THEN the two readings of runners/train_reference_checked.py on
parameters that carry the same balanced bias: the first step's GRADIENT
leaf by leaf (`check.grad_tol`) and the state-space scan ALONE
(`check.scan_tol`). It composes those two runners and copies no loop:
both modules are loaded, the first's `run` runs, and the second's
`built`, `B1`, `errors_by_leaf` and `verdict` are used as they stand.

  * `first_gradient_is_the_reference`: the program's own FIRST train step
    run once more on parameters made again from the seed with the bias
    the loop started from (`make_train_step` on `llama.loss_and_weight_fn`
    with AdamW, as the loop builds it: the timed program itself; AdamW's
    first moment after one step from fresh moments is (1 - b1) x the
    gradient) against `reference.grads` (reverse mode through the
    position-by-position equations, float32 at `highest`), given the same
    bias: a leaf of the parameter tree |g - g_ref|_2 / |g_ref|_2, the
    worst within `check.grad_tol`.
  * `first_scan_is_the_reference`: what layer 0 hands its scan for
    sequence 0 (`reference.first_scan`: x, dt, A, B, C, D at the timed
    length) through the function the program's sublayer calls (the model
    module's own name `ssd_scan`: a sublayer that calls another breaks
    this loudly) against `reference.recurrence`, forward (y) and backward
    (a seeded cotangent pulled back to x, dt, B, C): the worst of the
    five within `check.scan_tol`, which a bfloat16 state and a scan in
    bfloat16 throughout each fail (`check.scan_tol_why`).

chipbench/tools/nemotron_h_wrong.py puts the reference computed in a
lower precision, and wrong in one thing at a time, through these same
functions and limits."""

from __future__ import annotations

import gc
import importlib
import time

from chipbench import manifest as mf

SCAN_OUTPUTS = ("y", "dx", "ddt", "dB", "dC")


def with_bias(params, bias):
    """`params` with the selection biases `bias` [expert layers, experts] in their place."""
    import jax.numpy as jnp

    table = jnp.asarray(bias, params["layers"]["router_bias"].dtype)
    return {**params, "layers": {**params["layers"], "router_bias": table}}


def moved_share(counts, reference_counts) -> float:
    """Of all (layer, token, choice) pairs, the share whose expert MOVED
    between two `tokens_per_expert` tables (half the sum of their
    differences: a lower bound): runners/train_reference_from_config.py's
    reading of the first step's routing, for the one-thing-wrong tool."""
    import numpy as np

    got, want = np.asarray(counts, np.int64), np.asarray(reference_counts, np.int64)
    return float(int(np.abs(got - want).sum()) // 2 / int(want.sum()))


def program_gradient(ctx: dict, checked, seed: int, bias, with_counts: bool = False):
    """(parameters with `bias`, batch 0, the gradient of the program's own
    FIRST train step on them, its loss[, its `tokens_per_expert`]):
    train_reference_checked.py's reading, from the balanced start the
    loop took."""
    import jax
    import optax

    from ray_tpu.models import llama
    from ray_tpu.train.step import TrainState, make_train_step

    cfg, init, batch_of = checked.built(ctx)
    opt = optax.adamw(ctx["config"]["train"]["lr"], b1=checked.B1)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)
    fresh, key, batch = jax.jit(init), jax.random.key(seed % (2 ** 31)), batch_of(seed)
    state, metrics = step(TrainState.create(with_bias(fresh(key), bias), opt), batch)  # donated
    grads = jax.tree.map(lambda mu: mu / (1 - checked.B1), state.opt_state[0].mu)
    loss = float(metrics["loss"])
    del state
    out = (with_bias(fresh(key), bias), batch, grads, loss)
    return (*out, jax.device_get(metrics["stats"]["tokens_per_expert"])) if with_counts else out


def scan_cotangent(tokens, config: dict, seed: int):
    """A cotangent of the scan's output for sequence 0, [S, H, P] float32, from the seed."""
    import jax

    return jax.random.normal(jax.random.key(seed % (2 ** 31) + 1), (
        tokens.shape[1], config["mamba_num_heads"], config["mamba_head_dim"]))


def program_scan(stack_module: str, chunk: int, args, w) -> dict:
    """{y, dx, ddt, dB, dC} of the function the program's Mamba sublayer
    calls, on the reference's arrays (x [S, H, P], dt [S, H], A [H], B, C
    [S, G, N], D [H]; the program's are [B, H, S, P], [B, H, S], [B, G, S,
    N]): forward, and w pulled back."""
    import jax
    import jax.numpy as jnp

    scan = importlib.import_module(stack_module).ssd_scan
    x, dt, A, B, C, D = args

    def on_one_sequence(x, dt, B, C):
        heads_first = lambda a: jnp.moveaxis(a, 0, 1)[None]  # noqa: E731
        y = scan(heads_first(x), heads_first(dt), A, heads_first(B), heads_first(C), D, chunk=chunk)
        return jnp.moveaxis(y[0], 0, 1)

    def both(x, dt, B, C, w):
        y, pull = jax.vjp(on_one_sequence, x, dt, B, C)
        return dict(zip(SCAN_OUTPUTS, (y,) + pull(w.astype(y.dtype))))

    return jax.jit(both)(x, dt, B, C, w)


def run(ctx: dict) -> dict:
    from_config = mf.load_plugin(ctx["root"], "runners", "train_reference_from_config")
    checked = mf.load_plugin(ctx["root"], "runners", "train_reference_checked")
    run = from_config.run(ctx)
    bias = from_config._BIAS[0]
    gc.collect()
    reference = importlib.import_module(f"chipbench.reference.{ctx['config']['reference']}")
    seed, config, check = ctx["args"].seed, ctx["config"], ctx["config"]["check"]
    t0 = time.monotonic()
    params, batch, grads, loss = program_gradient(ctx, checked, seed, bias)
    t1 = time.monotonic()
    gradient = checked.errors_by_leaf(
        grads, reference.grads(params, batch["tokens"], batch["targets"], config))
    del grads
    t2 = time.monotonic()
    w = scan_cotangent(batch["tokens"], config, seed)
    args, outputs = reference.first_scan(params, batch["tokens"][0], config, w)
    scan = checked.errors_by_leaf(
        program_scan(checked.built(ctx)[0].stack_module, config["chunk_size"], args, w),
        dict(zip(SCAN_OUTPUTS, outputs)))
    of_gradient = checked.verdict(gradient, check["grad_tol"])
    of_scan = checked.verdict(scan, check["scan_tol"])
    ctx["log"](event="correct_gradient", **of_gradient, errors=gradient, loss_of_this_step=loss,
               first_loss=run["losses"][0], program_s=round(t1 - t0, 1),
               reference_s=round(t2 - t1, 1))
    ctx["log"](event="correct_scan", **of_scan, errors=scan, seconds=round(time.monotonic() - t2, 1))
    run["checks"]["first_gradient_is_the_reference"] = of_gradient["ok"]
    run["checks"]["first_scan_is_the_reference"] = of_scan["ok"]
    run["correct"] = all(run["checks"].values())
    return run
