"""The runner of a cell whose `correct` holds the first step's loss,
routing, dropless counts AND gradient, and nothing of a kernel alone:
runners/train_reference_from_config.py (the selection bias BALANCED
between the init and the first step, the first loss within
`check.loss_tol`, the first step's routing within `check.routing_tol`, no
dropped pair and every pair counted in every step), THEN the first
reading of runners/train_reference_checked.py on parameters that carry
the same balanced bias: the first step's GRADIENT leaf by leaf
(`check.grad_tol`). It composes the runners that are there and copies no
loop, as runners/train_reference_nemotron_h.py does, whose
`program_gradient` (the timed program's own first train step run once
more from the balanced start) it uses as it stands; that runner's second
reading, a scan alone, has nothing to read in a stack of attention and
expert layers, which is all this one leaves out.

  * `first_gradient_is_the_reference`: AdamW's first moment after one
    step from fresh moments is (1 - b1) x the gradient the timed program
    was handed; against `reference.grads` (reverse mode through the plain
    equations, float32 at `highest`), given the same bias: a leaf of the
    parameter tree |g - g_ref|_2 / |g_ref|_2, the worst within
    `check.grad_tol`.

chipbench/tools/mellum2_wrong.py puts the reference computed in a lower
precision, and wrong in one thing at a time, through these same
functions and limits."""

from __future__ import annotations

import gc
import importlib
import time

from chipbench import manifest as mf


def run(ctx: dict) -> dict:
    from_config = mf.load_plugin(ctx["root"], "runners", "train_reference_from_config")
    checked = mf.load_plugin(ctx["root"], "runners", "train_reference_checked")
    composed = mf.load_plugin(ctx["root"], "runners", "train_reference_nemotron_h")
    run = from_config.run(ctx)
    bias = from_config._BIAS[0]
    gc.collect()
    reference = importlib.import_module(f"chipbench.reference.{ctx['config']['reference']}")
    seed, config = ctx["args"].seed, ctx["config"]
    t0 = time.monotonic()
    params, batch, grads, loss = composed.program_gradient(ctx, checked, seed, bias)
    t1 = time.monotonic()
    gradient = checked.errors_by_leaf(
        grads, reference.grads(params, batch["tokens"], batch["targets"], config))
    del grads, params
    of_gradient = checked.verdict(gradient, config["check"]["grad_tol"])
    ctx["log"](event="correct_gradient", **of_gradient, errors=gradient, loss_of_this_step=loss,
               first_loss=run["losses"][0], program_s=round(t1 - t0, 1),
               reference_s=round(time.monotonic() - t1, 1))
    run["checks"]["first_gradient_is_the_reference"] = of_gradient["ok"]
    run["correct"] = all(run["checks"].values())
    return run
