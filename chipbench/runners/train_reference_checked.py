"""runners/train_reference.py with its two constants read from the
configuration file, `check.loss_tol` (the tolerance of the step's own
first loss against the reference, written there with the readings it
was set from) and `check.scopes` (the named scopes a traced run reads
from the compiled step's text), AND a second number in `correct`: the
first step's GRADIENT against the reference's, leaf by leaf.

That runner fixes both constants for OLMoE and may not be edited by a PR
that adds a configuration: its module is loaded, the constants are set
on it, and its `run` runs. The trainer, the loop, the window's
arithmetic, the reference's loss and the checks it makes are that
runner's: this is no copy of the loop (runners/train_reference_from_config.py
is one, for a router's selection bias set between the init and the first
step, which a dense stack has not).

TWO MORE NUMBERS. The first loss of random weights is ln(vocabulary) + a
small number: it passes a rotary put into the full layer on two seeds of
four, a reference computed in bfloat16 throughout and a bfloat16 state
(the file's `check.loss_tol_why`), and it says nothing of the backward,
which is two of the recurrence's three passes. So, after the window and
that runner's own checks, on parameters and batch 0 made again from the
seed at the timed sizes:

  * `first_gradient_is_the_reference`: the program's own FIRST train step
    run once more (`make_train_step` on `llama.loss_and_weight_fn` with
    AdamW, as the loop builds it: the timed program itself, its loss the
    timed step's first loss bit for bit; AdamW's first moment after one
    step from fresh moments is (1 - b1) x the gradient it was handed)
    against `reference.grads` (reverse mode through the
    position-by-position equations, float32 at `highest`): a leaf of the
    parameter tree |g - g_ref|_2 / |g_ref|_2 (`errors_by_leaf`), the
    worst of the 68 within `check.grad_tol`. It refuses every wrong
    mechanism of the table on every seed, the rotary among them. It
    CANNOT see a precision: the program's own bf16 compute is 0.4-10% of
    a leaf, an all-bfloat16 reference reads the same and a bfloat16 state
    adds a third of that in quadrature (`check.grad_tol_why`).
  * `first_rule_is_the_reference`: so the recurrence is also held ALONE,
    on the same inputs, where no other rounding stands between the two:
    what layer 0 hands its rule for sequence 0 (`reference.first_rule`:
    q, k, v, g, beta at the timed length) through the function the
    program's sublayer calls (the model module's own name
    `gated_delta_rule`: a sublayer that calls another breaks this
    loudly) against `reference.recurrence`, forward (o) and backward (a
    seeded cotangent pulled back to q, k, v, g, beta): the worst of the
    six within `check.rule_tol`, which a bfloat16 state and a rule in
    bfloat16 throughout each fail (`check.rule_tol_why`).

chipbench/tools/olmo_hybrid_wrong.py puts the reference computed in a
lower precision, and wrong in one thing at a time, through these same
functions and limits.

One thing more, as that third runner does and for its reason (its
docstring, `# steady`): before the window everything alive is collected
once and kept out of the collector's sight (`gc.freeze`). A process with
JAX and the runtime loaded holds so many objects that one full
collection takes 70 ms or more, and one inside a 10 s window of steps
that each end in a host sync reads `train_tok_s` 0.7-1.1% low (two runs
of this cell's first twelve; with the objects frozen when the loop
STARTS, one run of the next twelve still lost 107 ms in one step; my chip
runs, PR 46, calls 4 and 7; as it stands, none of twelve, call 9). The
loop has no seam before its window, so the two lines stand behind its
own last report of a warm step, `session.report` wrapped for the loop's
length: the last thing that runs before the window but a
`block_until_ready`. If that report never comes (the loop's phases or
keys renamed) the run FAILS: a window read with the collector running is
another measurement, not to be booked under this cell's name. (For the
`benchmark` PR: a seam in `train_reference.train_loop` before its window,
and its limits read from `check`, would make this wrapper's patching and
the third runner's copy of the loop both unnecessary; PERF.md section 7
item 4.)"""

from __future__ import annotations

import gc
import time

from chipbench import manifest as mf

_BASE: list = []  # runners/train_reference.py's module and its own loop, for the length of a run
_STEADIED: list = []  # the objects frozen before the window, one entry a loop that got there


def train_loop(c: dict) -> None:
    """That runner's loop, one collection behind its last warm step's
    report (a function of this module, so that the trainer finds it by
    name)."""
    from ray_tpu.train import session

    base, loop = _BASE
    report = session.report

    def report_then_steady(metrics, *args, **kwargs):
        out = report(metrics, *args, **kwargs)
        if metrics.get("phase") == "warm" and metrics.get("step") == base.WARM_STEPS:
            gc.collect()  # steady
            gc.freeze()  # steady
            _STEADIED.append(gc.get_freeze_count())
        return out

    session.report = report_then_steady
    try:
        loop(c)
    finally:
        session.report = report


def errors_by_leaf(got, want) -> dict:
    """{a leaf's path: |got - want|_2 / |want|_2} over two trees of one
    structure, computed where the trees are (a leaf whose reference is all
    zeros reads |got|_2)."""
    import jax
    import jax.numpy as jnp

    def one(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        norm = jnp.sqrt(jnp.sum(jnp.square(w)))
        return jnp.sqrt(jnp.sum(jnp.square(g - w))) / jnp.where(norm > 0, norm, 1.0)

    errors = jax.device_get(jax.jit(lambda a, b: jax.tree.map(one, a, b))(got, want))
    return {jax.tree_util.keystr(path): float(e)
            for path, e in jax.tree_util.tree_leaves_with_path(errors)}


def verdict(errors: dict, tolerance: float) -> dict:
    """The worst of the errors and whether it is within the tolerance. A
    reading that is not a number is not within it."""
    leaf = max(errors, key=lambda k: errors[k] if errors[k] == errors[k] else float("inf"))
    return {"ok": bool(errors[leaf] <= tolerance), "worst": leaf, "err": errors[leaf],
            "tolerance": tolerance, "leaves": len(errors)}


def built(ctx: dict):
    """(the program's configuration, its init, batch 0's maker) as the loop builds them."""
    config, traffic = ctx["config"], ctx["traffic"]
    builder = mf.load_plugin(ctx["root"], "model_builders", config["model_builder"])
    gen = mf.load_plugin(ctx["root"], "generators", traffic["generator"])
    cfg, init, _ = builder.build(config, attention_impl=config["train"]["attention_impl"])
    return cfg, init, lambda seed: gen.batch_fn(traffic, cfg.vocab_size,
                                                config["train"]["global_batch"], seed)(0)


B1 = 0.9  # optax.adamw's default, spelled out: the first step's gradient is read back through it


def program_gradient(ctx: dict, seed: int):
    """(parameters, batch 0, the gradient of the program's own FIRST train
    step on them, its loss). The step is built as the loop builds it,
    AdamW and all, so it is the timed program itself (the compile cache
    hands it back, and `obs.op_names()`, which a traced run's readers ask
    after this, still describes the timed step); after one step from fresh
    moments AdamW's first moment is (1 - b1) x the gradient."""
    import jax
    import optax

    from ray_tpu.models import llama
    from ray_tpu.train.step import TrainState, make_train_step

    cfg, init, batch_of = built(ctx)
    opt = optax.adamw(ctx["config"]["train"]["lr"], b1=B1)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt)

    fresh, key, batch = jax.jit(init), jax.random.key(seed % (2 ** 31)), batch_of(seed)
    state, metrics = step(TrainState.create(fresh(key), opt), batch)  # the state is donated
    grads = jax.tree.map(lambda mu: mu / (1 - B1), state.opt_state[0].mu)
    loss = float(metrics["loss"])
    del state
    return fresh(key), batch, grads, loss


RULE_OUTPUTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


def rule_cotangent(tokens, config: dict, seed: int):
    """A cotangent of the rule's output for sequence 0, [S, H, dv] float32, from the seed."""
    import jax

    return jax.random.normal(jax.random.key(seed % (2 ** 31) + 1), (
        tokens.shape[1], config["linear_num_value_heads"], config["linear_value_head_dim"]))


def program_rule(ctx: dict, args, w) -> dict:
    """{o, dq, dk, dv, dg, dbeta} of the function the program's linear
    sublayer calls, on the reference's arrays [S, H, d] (the program's are
    [B, H, S, d]): forward, and w pulled back."""
    import importlib

    import jax
    import jax.numpy as jnp

    rule = importlib.import_module(built(ctx)[0].stack_module).gated_delta_rule

    def on_one_sequence(*args):
        return jnp.moveaxis(rule(*(jnp.moveaxis(a, 0, 1)[None] for a in args))[0], 0, 1)

    def both(args, w):
        o, pull = jax.vjp(on_one_sequence, *args)
        return dict(zip(RULE_OUTPUTS, (o,) + pull(w.astype(o.dtype))))

    return jax.jit(both)(args, w)


def run(ctx: dict) -> dict:
    import importlib

    base = mf.load_plugin(ctx["root"], "runners", "train_reference")
    check = ctx["config"]["check"]
    base.LOSS_TOL = float(check["loss_tol"])
    base.SCOPES = tuple(check["scopes"])
    _BASE[:] = [base, base.train_loop]
    _STEADIED.clear()
    base.train_loop = train_loop
    run = base.run(ctx)
    if not _STEADIED:
        raise RuntimeError("the collector was never rested before the window: runners/"
                           "train_reference.py no longer reports its last warm step as "
                           "{'phase': 'warm', 'step': WARM_STEPS}")
    ctx["log"](event="steady", frozen_objects=_STEADIED[-1])
    gc.collect()
    reference = importlib.import_module(f"chipbench.reference.{ctx['config']['reference']}")
    seed, config = ctx["args"].seed, ctx["config"]
    t0 = time.monotonic()
    params, batch, grads, loss = program_gradient(ctx, seed)
    t1 = time.monotonic()
    gradient = errors_by_leaf(grads, reference.grads(params, batch["tokens"], batch["targets"],
                                                     config))
    del grads
    t2 = time.monotonic()
    w = rule_cotangent(batch["tokens"], config, seed)
    args, outputs = reference.first_rule(params, batch["tokens"][0], config, w)
    rule = errors_by_leaf(program_rule(ctx, args, w), dict(zip(RULE_OUTPUTS, outputs)))
    of_gradient, of_rule = verdict(gradient, check["grad_tol"]), verdict(rule, check["rule_tol"])
    ctx["log"](event="correct_gradient", **of_gradient, errors=gradient, loss_of_this_step=loss,
               first_loss=run["losses"][0], program_s=round(t1 - t0, 1),
               reference_s=round(t2 - t1, 1))
    ctx["log"](event="correct_rule", **of_rule, errors=rule, seconds=round(time.monotonic() - t2, 1))
    run["checks"]["first_gradient_is_the_reference"] = of_gradient["ok"]
    run["checks"]["first_rule_is_the_reference"] = of_rule["ok"]
    run["correct"] = all(run["checks"].values())
    return run
