"""The runner of a Kimi-Linear cell: runners/train_reference_from_config.py
(the selection bias BALANCED between the init and the first step, the
first loss within `check.loss_tol`, the first step's routing within
`check.routing_tol`, no dropped pair and every pair counted in every
step), THEN three more readings on parameters that carry the same balanced
bias: the first step's GRADIENT leaf by leaf (`check.grad_tol`,
`check.routed_grad_tol`), the KDA rule ALONE at 32 heads
(`check.rule_tol`) and the MLA layer's ATTENTION alone
(`check.attention_tol`). It composes the runners that stand and copies no
loop: `train_reference_from_config.run` runs;
`train_reference_nemotron_h.program_gradient`,
`train_reference_checked.errors_by_leaf` / `verdict` and
`train_reference_solar_open2.rule_cotangent` / `program_rule` are used as
they stand. (That last runner's own `run` is not reused: it knows no
attention reading, and its `gradient_verdict` leaves the first layer of ONE
period out of every limit, which this stack does not need: the first
expert layer reads rows that a KDA state has already made unlike.)

  * `first_gradient_is_the_reference`: the program's own FIRST train step
    run once more on parameters made again from the seed with the bias the
    loop started from, against `reference.grads` (reverse mode through the
    position-by-position equations, float32 at `highest`), given the same
    bias: a leaf of the parameter tree |g - g_ref|_2 / |g_ref|_2. EVERY
    leaf is read and logged and holds a limit (`gradient_verdict`):
    `check.grad_tol` the leaves that no routing decision multiplies (the
    mixers', the dense SwiGLU's, the shared experts', the norms', the
    tables'), `check.routed_grad_tol` the routed leaves (`router`, `w_gate`,
    `w_up`, `w_down`) of every expert layer: the wider, because the bf16
    stream moves some pairs in a hundred to another expert than the float32
    reference's and this chip holds a thirty-second of the pairs
    (`check.routed_grad_tol_why`).
  * `first_rule_is_the_reference`: what layer 1 (the dense layer's KDA
    mixer) hands its rule for sequence 0 (`reference.first_rule`) through
    the function the program's sublayer calls (the model module's own name
    `kda_rule`) against `reference.recurrence`, forward and a seeded
    cotangent pulled back to q, k, v, g, beta: the worst of the six within
    `check.rule_tol`.
  * `first_attention_is_the_reference`: what layer 4 (the first MLA layer)
    hands its attention for sequence 0 (`reference.first_attention`: q, k
    [S, 32, 192], v [S, 32, 128], rounded to bfloat16 as the program's
    kernels are handed them) through the function the program's MLA
    sublayer calls (`ray_tpu.models.mla.attention_head_major` with
    `impl="flash"`, by that name: ops/flash.py's kernels at keys of 192 and
    values of 128) against a float32 softmax over the same rounded inputs, a
    block of queries at a time: forward (o) and a seeded cotangent pulled
    back to q, k, v: the worst of the four within `check.attention_tol`.

chipbench/tools/kimi_linear_wrong.py puts the reference computed in a
lower precision, and wrong in one thing at a time, through these same
functions and limits."""

from __future__ import annotations

import gc
import importlib
import math
import time

from chipbench import manifest as mf

ATTENTION_OUTPUTS = ("o", "dq", "dk", "dv")
# the leaves of a layer whose gradient a routing decision multiplies
ROUTED = ("['router']", "['w_gate']", "['w_up']", "['w_down']")


def gradient_verdict(checked, errors: dict, check: dict) -> dict:
    """`checked.verdict` of the leaves no routing decision multiplies within
    `check.grad_tol` (its keys as they stand), AND the routed leaves of the
    expert layers within `check.routed_grad_tol` (`routed_*`). A dense
    layer's `w_gate` / `w_up` / `w_down` are no routed leaves."""
    routed = {k: v for k, v in errors.items() if k.endswith(ROUTED) and "dense_layers" not in k}
    out = checked.verdict({k: v for k, v in errors.items() if k not in routed}, check["grad_tol"])
    of = checked.verdict(routed, check["routed_grad_tol"])
    out.update(routed_worst=of["worst"], routed_err=of["err"],
               routed_tolerance=check["routed_grad_tol"], routed_leaves=len(routed))
    out["ok"] = out["ok"] and of["ok"] and math.isfinite(of["err"])
    return out


def attention_cotangent(tokens, config: dict, seed: int):
    """A cotangent of the attention's output for sequence 0, [S, H, d_v] float32, from the seed."""
    import jax

    return jax.random.normal(jax.random.key(seed % (2 ** 31) + 2), (
        tokens.shape[1], config["num_attention_heads"], config["v_head_dim"]))


def program_attention(args, w, dtype="bfloat16") -> dict:
    """{o, dq, dk, dv} of the function the program's MLA sublayer calls
    (models/mla.py's own name `attention_head_major`, the flash kernels), on
    the reference's arrays (q, k [S, H, dk], v [S, H, dv], already rounded to
    `dtype`; the program's are [B, H, S, ...] in `dtype`): forward, and w
    pulled back; float32 out."""
    import jax
    import jax.numpy as jnp

    attend = importlib.import_module("ray_tpu.models.mla").attention_head_major

    def on_one_sequence(q, k, v):
        q, k, v = (jnp.moveaxis(a, 0, 1)[None].astype(dtype) for a in (q, k, v))
        o = attend(q, k, v, causal=True, segment_ids=None, impl="flash")
        return jnp.moveaxis(o[0], 0, 1).astype(jnp.float32)

    def both(args, w):
        o, pull = jax.vjp(on_one_sequence, *args)
        return dict(zip(ATTENTION_OUTPUTS, (o,) + pull(w.astype(o.dtype))))

    return jax.jit(both)(args, w)


def run(ctx: dict) -> dict:
    from_config = mf.load_plugin(ctx["root"], "runners", "train_reference_from_config")
    checked = mf.load_plugin(ctx["root"], "runners", "train_reference_checked")
    shared = mf.load_plugin(ctx["root"], "runners", "train_reference_nemotron_h")
    solar = mf.load_plugin(ctx["root"], "runners", "train_reference_solar_open2")
    run = from_config.run(ctx)
    bias = from_config._BIAS[0]
    gc.collect()
    reference = importlib.import_module(f"chipbench.reference.{ctx['config']['reference']}")
    seed, config, check = ctx["args"].seed, ctx["config"], ctx["config"]["check"]
    t0 = time.monotonic()
    params, batch, grads, loss = shared.program_gradient(ctx, checked, seed, bias)
    t1 = time.monotonic()
    gradient = checked.errors_by_leaf(
        grads, reference.grads(params, batch["tokens"], batch["targets"], config))
    del grads
    t2 = time.monotonic()
    w = solar.rule_cotangent(batch["tokens"], config, seed)
    args, outputs = reference.first_rule(params, batch["tokens"][0], config, w)
    rule = checked.errors_by_leaf(
        solar.program_rule(checked.built(ctx)[0].stack_module, args, w),
        dict(zip(solar.RULE_OUTPUTS, outputs)))
    t3 = time.monotonic()
    w = attention_cotangent(batch["tokens"], config, seed)
    args, outputs = reference.first_attention(params, batch["tokens"][0], config, w)
    attention = checked.errors_by_leaf(program_attention(args, w),
                                       dict(zip(ATTENTION_OUTPUTS, outputs)))
    of_gradient = gradient_verdict(checked, gradient, check)
    of_rule = checked.verdict(rule, check["rule_tol"])
    of_attention = checked.verdict(attention, check["attention_tol"])
    ctx["log"](event="correct_gradient", **of_gradient, errors=gradient, loss_of_this_step=loss,
               first_loss=run["losses"][0], program_s=round(t1 - t0, 1),
               reference_s=round(t2 - t1, 1))
    ctx["log"](event="correct_rule", **of_rule, errors=rule, seconds=round(t3 - t2, 1))
    ctx["log"](event="correct_attention", **of_attention, errors=attention,
               seconds=round(time.monotonic() - t3, 1))
    run["checks"]["first_gradient_is_the_reference"] = of_gradient["ok"]
    run["checks"]["first_rule_is_the_reference"] = of_rule["ok"]
    run["checks"]["first_attention_is_the_reference"] = of_attention["ok"]
    run["correct"] = all(run["checks"].values())
    return run
