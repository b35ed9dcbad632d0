"""The runner of a Solar-Open2 cell: runners/train_reference_from_config.py
(the selection bias BALANCED between the init and the first step, the
first loss within `check.loss_tol`, the first step's routing within
`check.routing_tol`, no dropped pair and every pair counted in every
step), THEN two more readings on parameters that carry the same balanced
bias: the first step's GRADIENT leaf by leaf (`check.grad_tol`) and the
KDA rule ALONE (`check.rule_tol`). It composes the runners that stand and
copies no loop: `train_reference_from_config.run` runs, and
`train_reference_nemotron_h.program_gradient` and
`train_reference_checked.errors_by_leaf` / `verdict` are used as they
stand.

  * `first_gradient_is_the_reference`: the program's own FIRST train step
    run once more on parameters made again from the seed with the bias
    the loop started from (`make_train_step` on `llama.loss_and_weight_fn`
    with AdamW, as the loop builds it: the timed program itself) against
    `reference.grads` (reverse mode through the position-by-position
    equations, float32 at `highest`), given the same bias: a leaf of the
    parameter tree |g - g_ref|_2 / |g_ref|_2. EVERY leaf is read and
    logged, and 89 of the 93 hold a limit (`gradient_verdict`):
    `check.grad_tol` the 77 that no routing decision multiplies (the
    mixers', the shared experts', the norms', the tables'),
    `check.routed_grad_tol` the 12 routed leaves (`router`, `w_gate`,
    `w_up`, `w_down`) of the layers after the first. The routed leaves'
    limit is the wider because a sound step
    reads more there, and why is SHOWN (`check.grad_tol_why`;
    chipbench/tools/solar_open2_wrong.py's row of the float32 reference
    against ITSELF under a bfloat16 stream's choice of experts): the bf16
    stream moves about 1 pair in 100 to another expert than the float32
    reference's, this chip holds a FORTIETH of the pairs, and those few
    moved pairs alone move a routed leaf by a tenth to a half. The FIRST
    layer's four routed leaves must read a number and hold NO limit: its
    router reads rows that Zipf traffic makes alike a thousand at a time
    (no rotary, a fresh GQA layer), such a block moves whole, and a sound
    step reads up to 0.90 there, over what several wrong mechanisms read.
    (The step's OWN choice cannot be handed to the reference: the step
    reports counts, not choices, and the same bf16 forward compiled as
    another program already chooses otherwise for 6-9 pairs in 1,000.)
  * `first_rule_is_the_reference`: what layer 1 (the first KDA layer)
    hands its rule for sequence 0 (`reference.first_rule`: q, k, v, g, beta
    at the timed length, g a VECTOR a head and position) through the
    function the program's sublayer calls (the model module's own name
    `kda_rule`: a sublayer that calls another breaks this loudly) against
    `reference.recurrence`, forward (o) and backward (a seeded cotangent
    pulled back to q, k, v, g, beta): the worst of the six within
    `check.rule_tol`, which the scalar rule (the decay's mean over a
    head's channels), a bfloat16 state and a rule in bfloat16 throughout
    each fail (`check.rule_tol_why`).

chipbench/tools/solar_open2_wrong.py puts the reference computed in a
lower precision, and wrong in one thing at a time, through these same
functions and limits."""

from __future__ import annotations

import gc
import importlib
import math
import time

from chipbench import manifest as mf

RULE_OUTPUTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


# the leaves of a layer whose gradient a routing decision multiplies
ROUTED = ("['router']", "['w_gate']", "['w_up']", "['w_down']")
FIRST_LAYER = "['period']['0']"   # the cell runs ONE period: its position 0 is layer 0


def gradient_verdict(checked, errors: dict, check: dict) -> dict:
    """`checked.verdict` of the leaves no routing decision multiplies within
    `check.grad_tol` (its keys as they stand), AND the routed leaves of the
    layers after the first within `check.routed_grad_tol` (`routed_*`); the
    first layer's routed leaves beside them (`first_layer_*`), which must be
    numbers and hold no limit."""
    routed = {k: v for k, v in errors.items() if k.endswith(ROUTED)}
    out = checked.verdict({k: v for k, v in errors.items() if k not in routed}, check["grad_tol"])
    for name, first, limit in (("routed", False, check["routed_grad_tol"]),
                               ("first_layer", True, None)):
        group = {k: v for k, v in routed.items() if (FIRST_LAYER in k) == first}
        if group:
            of = checked.verdict(group, math.inf if limit is None else limit)
            out.update({f"{name}_worst": of["worst"], f"{name}_err": of["err"],
                        f"{name}_tolerance": limit, f"{name}_leaves": len(group)})
            out["ok"] = out["ok"] and of["ok"] and math.isfinite(of["err"])
    return out


def rule_cotangent(tokens, config: dict, seed: int):
    """A cotangent of the rule's output for sequence 0, [S, H, d] float32, from the seed."""
    import jax

    lin = config["linear_attn_config"]
    return jax.random.normal(jax.random.key(seed % (2 ** 31) + 1),
                             (tokens.shape[1], lin["num_heads"], lin["head_dim"]))


def program_rule(stack_module: str, args, w) -> dict:
    """{o, dq, dk, dv, dg, dbeta} of the function the program's KDA
    sublayer calls, on the reference's arrays (q, k, v, g [S, H, d], beta
    [S, H]; the program's are [B, H, S, ...]): forward, and w pulled back."""
    import jax
    import jax.numpy as jnp

    rule = importlib.import_module(stack_module).kda_rule

    def on_one_sequence(*args):
        return jnp.moveaxis(rule(*(jnp.moveaxis(a, 0, 1)[None] for a in args))[0], 0, 1)

    def both(args, w):
        o, pull = jax.vjp(on_one_sequence, *args)
        return dict(zip(RULE_OUTPUTS, (o,) + pull(w.astype(o.dtype))))

    return jax.jit(both)(args, w)


def run(ctx: dict) -> dict:
    from_config = mf.load_plugin(ctx["root"], "runners", "train_reference_from_config")
    checked = mf.load_plugin(ctx["root"], "runners", "train_reference_checked")
    shared = mf.load_plugin(ctx["root"], "runners", "train_reference_nemotron_h")
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
    w = rule_cotangent(batch["tokens"], config, seed)
    args, outputs = reference.first_rule(params, batch["tokens"][0], config, w)
    rule = checked.errors_by_leaf(program_rule(checked.built(ctx)[0].stack_module, args, w),
                                  dict(zip(RULE_OUTPUTS, outputs)))
    of_gradient = gradient_verdict(checked, gradient, check)
    of_rule = checked.verdict(rule, check["rule_tol"])
    ctx["log"](event="correct_gradient", **of_gradient, errors=gradient, loss_of_this_step=loss,
               first_loss=run["losses"][0], program_s=round(t1 - t0, 1),
               reference_s=round(t2 - t1, 1))
    ctx["log"](event="correct_rule", **of_rule, errors=rule, seconds=round(time.monotonic() - t2, 1))
    run["checks"]["first_gradient_is_the_reference"] = of_gradient["ok"]
    run["checks"]["first_rule_is_the_reference"] = of_rule["ok"]
    run["correct"] = all(run["checks"].values())
    return run
