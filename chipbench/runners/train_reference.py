"""Runner of a training cell whose architecture is named in its
configuration file: the model builder AND the plain reference are found
by name (`model_builder`, `reference` = a module of chipbench/reference
with `loss(params, tokens, targets, config)`), so the next architecture
brings a builder and a reference and no runner.

It goes as runners/train.py goes, through JaxTrainer(...).fit() in the
in-process runtime, one worker with the cell's chips, the program's own
train step (make_train_step, AdamW, flash attention) on batches made on
the device, every step reported through session.report; the same
window (every step that STARTS within --seconds, the profiler's start
and stop off its clock), the same keys in what it returns, so the
readers written for that runner read this one. Two differences:

  * the loss function is llama.loss_and_weight_fn, whose third element
    (an expert configuration's statistics) the step hands out as
    metrics["stats"]; each step's are reported with its loss;
  * a traced run reads the compiled step's HLO text for the named scope
    of each instruction (chipbench/hlo_scopes.py), which the profiler's
    trace drops.

correct: what runners/train.py checks (every loss finite; the mean of
the last ten below the first; the measured step's OWN first loss within
LOSS_TOL of the reference on the same parameters and batch, one
sequence at a time, after the window); and, where the step reports
expert statistics, in EVERY step no dropped pair and every layer's
tokens per expert summing to num_experts_per_tok x tokens.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import time

# Relative, of the step's own first loss (cross-entropy + the two router
# losses at their coefficients) against the reference's. The program
# computes in bf16 with fp32 accumulation (router, softmax and loss in
# fp32), the reference in fp32 at "highest" precision. Seen on the chip
# at olmoe-1b-7b's published widths, one layer (PR 26): 0.1e-5 to 1.7e-5
# over eight seeds at batch 6, 0.5e-5 to 1.9e-5 over three at batch 4.
# 6e-5 is three times the worst of those and under what it has to
# refuse: the same step with the top-8 weights renormalised reads
# 8.1e-5 to 80e-5 on those eleven seeds (every one refused), and the
# reference itself computed in bfloat16 throughout, the precision below
# the configuration's, 19e-5 to 860e-5. It cannot tell bf16 router
# logits from fp32 ones (0.2e-5 to 1.9e-5, three seeds): the bf16
# stream the router reads already moves as many of a batch's 131,072
# choices (115-172 against 208-300), and one layer's routing moves the
# loss of random weights by less than bf16's own rounding does.
LOSS_TOL = 6e-5
WARM_STEPS = 3
TRACED_STEPS = (3, 6)  # window steps [3, 6) are traced
SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine")


def router_summary(stats) -> dict | None:
    """A step's statistics tree (leaves stacked over layers) as plain
    numbers for session.report; None where the step reports none."""
    if stats is None:
        return None
    return {
        "dropped_pairs": int(stats["dropped_pairs"].sum()),
        "pairs": [int(x) for x in stats["tokens_per_expert"].sum(axis=-1)],
        "imbalance": [float(x) for x in stats["imbalance"]],
        "balance_loss": [float(x) for x in stats["balance_loss"]],
        "z_loss": [float(x) for x in stats["z_loss"]],
    }


def train_loop(c: dict) -> None:
    import jax
    import optax

    from chipbench import hlo_scopes, manifest as mf, phases, tracing

    with phases.phase("import_program"):
        from ray_tpu.models import llama
        from ray_tpu.train import session
        from ray_tpu.train.step import TrainState, init_sharded_params, make_train_step

    config, traffic, seed = c["config"], c["traffic"], c["seed"]
    tcfg = config["train"]
    with phases.phase("import_program"):
        # the builder's build() imports the model modules it needs
        builder = mf.load_plugin(c["root"], "model_builders", config["model_builder"])
        gen = mf.load_plugin(c["root"], "generators", traffic["generator"])
        cfg, init, axes = builder.build(config, attention_impl=tcfg["attention_impl"])
    key = jax.random.key(seed % (2 ** 31))
    mesh = rules = sharding = None
    if config.get("mesh"):
        from jax.sharding import NamedSharding

        with phases.phase("import_program"):
            from ray_tpu.parallel.mesh import MeshSpec, make_mesh
            from ray_tpu.parallel.sharding import default_rules

        mesh, rules = make_mesh(MeshSpec(**config["mesh"])), default_rules()
        sharding = NamedSharding(mesh, rules.spec(("batch", "seq")))
    opt = optax.adamw(tcfg["lr"])
    with phases.phase("init_params"):
        if mesh is not None:
            params = init_sharded_params(init, axes, mesh, rules, key)
        else:
            params = jax.jit(init)(key)
        state = TrainState.create(params, opt)
        del params
        jax.block_until_ready(state)
    step = make_train_step(lambda p, b: llama.loss_and_weight_fn(p, b, cfg), opt,
                           mesh=mesh, rules=rules)
    batch_size, seq = tcfg["global_batch"], traffic["seq_len"]
    make = gen.batch_fn(traffic, cfg.vocab_size, batch_size, seed, sharding)

    def one(i):
        with jax.profiler.TraceAnnotation("chipbench.make_batch"):
            batch = make(i)
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            new_state, m = step(state_box[0], batch)
        state_box[0] = new_state
        with jax.profiler.TraceAnnotation("chipbench.loss_sync"):
            # host transfer: the step has run
            loss, stats = jax.device_get((m["loss"], m.get("stats")))
        return float(loss), stats

    state_box = [state]
    del state
    i = 0
    first_counts = None
    for _ in range(1 + WARM_STEPS):
        # step 0 loads the step from the cache, or compiles it, and runs it first
        with phases.phase("first_step" if i == 0 else "warm_steps"):
            t = time.monotonic()
            loss, stats = one(i)
            if i == 0 and stats is not None:
                first_counts = stats["tokens_per_expert"].tolist()
            session.report({"phase": "warm", "step": i, "loss": loss,
                            "router": router_summary(stats), "step_s": time.monotonic() - t})
            i += 1
    with phases.phase("warm_steps"):
        jax.block_until_ready(state_box[0])
    # ---- the window -------------------------------------------------------
    trace_dir = os.path.join(c["out_dir"], "trace")
    trace_steps = TRACED_STEPS if c["trace"] else None
    setup_s, setup_phases = phases.setup_s(), phases.seconds()
    w0, t0 = time.time(), time.monotonic()
    paused = [0.0]  # seconds the profiler took to start and stop: not the step's

    def clock():
        return time.monotonic() - t0 - paused[0]

    def outside_the_clock(f, *a):
        t = time.monotonic()
        f(*a)
        paused[0] += time.monotonic() - t

    n = 0
    marker = None
    while clock() < c["seconds"]:
        if trace_steps and n == trace_steps[0]:
            outside_the_clock(tracing.start, trace_dir)
            marker = jax.profiler.TraceAnnotation("chipbench.window")
            marker.__enter__()
        a = clock()
        loss, stats = one(i)
        b = clock()
        with jax.profiler.TraceAnnotation("chipbench.report"):
            session.report({"phase": "window", "step": i, "loss": loss,
                            "router": router_summary(stats), "start": a, "end": b})
        i += 1
        n += 1
        if marker is not None and n == trace_steps[1]:
            jax.block_until_ready(state_box[0])
            marker.__exit__(None, None, None)
            marker = None
            outside_the_clock(tracing.stop)
    jax.block_until_ready(state_box[0])
    w1 = time.time()
    if marker is not None:  # a window shorter than the traced steps
        marker.__exit__(None, None, None)
        tracing.stop()
    mem = scopes = None
    if c["trace"]:
        # what the compiler says the step needs, beside memory_stats()'s
        # peak (which PR 21 found leaves the step's temporaries out), and
        # the scope of each of its instructions
        try:
            compiled = step.lower(state_box[0], make(0)).compile()
            ma = compiled.memory_analysis()
            mem = {k: getattr(ma, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")}
            scopes = hlo_scopes.scopes_of(compiled.as_text(), SCOPES)
        except Exception as e:  # noqa: BLE001 - an earlier line only
            mem = {"error": repr(e)}
    # the peak of the system under test: read before the reference
    # puts its own copy of the parameters on the first chip
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    session.report({"phase": "done", "setup_s": setup_s, "setup_phases": setup_phases,
                    "window_wall": (w0, w1),
                    "memory_analysis": mem, "scopes": scopes,
                    "tokens_per_step": batch_size * seq, "first_counts": first_counts,
                    "memory_peak_bytes": peak,
                    "platform": jax.devices()[0].platform})
    state_box.clear()


def run(ctx: dict) -> dict:
    import jax

    from chipbench import manifest as mf, phases, tracing

    with phases.phase("import_program"):
        import ray_tpu
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    args, config, traffic, chips = ctx["args"], ctx["config"], ctx["traffic"], ctx["chips"]
    # before the runtime starts: a program that does not know this
    # architecture fails here, at once
    reference = importlib.import_module(f"chipbench.reference.{config['reference']}")
    with phases.phase("import_program"):
        builder = mf.load_plugin(ctx["root"], "model_builders", config["model_builder"])
        gen = mf.load_plugin(ctx["root"], "generators", traffic["generator"])
        cfg, init, _ = builder.build(config)
    ray_tpu.init()
    deadline = time.monotonic() + 60
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        if time.monotonic() > deadline:
            raise RuntimeError(f"{chips} TPU chip(s) never became available")
        time.sleep(0.2)
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "root": ctx["root"], "config": config, "traffic": traffic,
            "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
            "out_dir": ctx["out_dir"],
        },
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True, chips_per_worker=chips),
        run_config=RunConfig(name=f"chipbench-{ctx['name']}"),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"trainer failed: {result.error!r}")
    ray_tpu.shutdown()
    hist = result.metrics_history
    done = [m for m in hist if m.get("phase") == "done"][-1]
    steps = [m for m in hist if m.get("phase") == "window"]
    stepped = [m for m in hist if "loss" in m]
    losses = [m["loss"] for m in stepped]
    tokens_per_step = done["tokens_per_step"]
    span_s = steps[-1]["end"] if steps else 0.0
    train_tok_s = len(steps) * tokens_per_step / span_s if span_s > 0 else None
    ctx["log"](event="train", steps_in_window=len(steps), window_s=span_s,
               step_s=[round(m["end"] - m["start"], 4) for m in steps][:40],
               losses=[round(x, 4) for x in losses][:64],
               memory_analysis=done["memory_analysis"],
               expected=gen.expected(traffic, config["vocab_size"], args.seed))

    # ---- correct: after the window, on parameters made again from the seed.
    # The step's own first loss is the program's word; the reference says
    # what it should have been, one sequence of batch 0 at a time.
    gc.collect()
    params = jax.jit(init)(jax.random.key(args.seed % (2 ** 31)))
    batch = gen.batch_fn(traffic, cfg.vocab_size, config["train"]["global_batch"],
                         args.seed)(0)
    ref = float(reference.loss(params, batch["tokens"], batch["targets"], config))
    del params
    first = losses[0]
    rel_err = abs(first - ref) / abs(ref)
    checks = {
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss_fell": sum(losses[-10:]) / len(losses[-10:]) < first,
        "first_loss_is_the_reference": rel_err <= LOSS_TOL,
        "steps_in_window": len(steps) > 0,
        "worker_on_tpu": done["platform"] == "tpu",
    }
    routed = [m["router"] for m in stepped if m.get("router") is not None]
    if routed:
        pairs = config["num_experts_per_tok"] * tokens_per_step
        checks["no_pair_dropped"] = all(r["dropped_pairs"] == 0 for r in routed)
        checks["every_pair_counted"] = all(p == pairs for r in routed for p in r["pairs"])
    ctx["log"](event="correct", checks=checks, first_loss=first, reference_loss=ref,
               rel_err=rel_err, tolerance=LOSS_TOL,
               reference_sequences=int(batch["tokens"].shape[0]),
               first_router=routed[0] if routed else None,
               first_tokens_per_expert=done["first_counts"])
    run = {
        "kind": "train", "correct": all(checks.values()), "checks": checks,
        "attempted": len(steps), "failed": 0,
        "values": {"train_tok_s": train_tok_s, "setup_s": done["setup_s"]},
        "setup_phases": done["setup_phases"],
        "window_wall": tuple(done["window_wall"]), "seconds": span_s,
        "shape": config, "traffic": traffic, "peaks": ctx["peaks"], "chips": chips,
        "steps": steps, "losses": losses, "tokens_per_step": tokens_per_step,
        "memory_analysis": done["memory_analysis"], "busy": None,
        "memory_peak_bytes": done["memory_peak_bytes"], "scopes": done["scopes"],
    }
    if args.trace:
        run.update(tracing.reduce(os.path.join(ctx["out_dir"], "trace"),
                                  ctx["names"], ctx["log"]))
        run["traced_steps"] = TRACED_STEPS[1] - TRACED_STEPS[0]
        run["traced_window_steps"] = steps[TRACED_STEPS[0]:TRACED_STEPS[1]]
    return run
