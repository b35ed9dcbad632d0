"""Runner of the training cells: JaxTrainer(...).fit() in the
in-process runtime, one worker with the cell's chips, the program's
own train step (make_train_step, AdamW, flash attention) on batches
made on the device, reporting every step through session.report as
users' loops do.

Window: opened after the compile and three warm steps, with
block_until_ready on the state; it takes every step that STARTS within
--seconds and closes when the last of them has ended (a step is 0.4 to
0.7 s long at these sizes, so cutting the window mid-step would make
the rate jump by one step's worth from run to run). train_tok_s is the
tokens of those steps over that time: all the work and all the time of
the window.

The profiler is kept out of that time: in a traced run the seconds
spent starting and stopping it are taken off the window's clock, so a
traced run steps for --seconds too and its rate (which train_mfu_pct
reads) is the untraced run's.

correct: every loss finite; the mean of the last ten losses below the
first; and the measured step's OWN first loss (what the sharded,
flash-attention step reported for batch 0 before any update) equal
within LOSS_TOL to the plain reference's loss on the same parameters
and batch, computed after the window (the train state freed, the
parameters made again from the seed) over every sequence of batch 0,
one sequence at a time.
"""

from __future__ import annotations

import gc
import math
import os
import time

# Relative. The program computes in bf16 with fp32 accumulation, the
# reference in fp32 at "highest" precision; bf16 rounding (eps 2^-8)
# averages out over the 12k-25k tokens of a batch. Seen on the chip
# (PR 23), the step's first loss against the reference: 0.8e-5 to
# 4.4e-5 over six seeds on one chip (2 layers), 0.4e-5 to 8.7e-5 over
# three seeds on four (8 layers, fsdp2 x tp2). 5e-4 is six times the
# worst of those. A wrong mask, shift or scale moves the loss by 1e-2
# or more, bf16 logits or a bf16 reduction by about 1e-3.
LOSS_TOL = 5e-4
WARM_STEPS = 3
TRACED_STEPS = (3, 6)  # window steps [3, 6) are traced


def train_loop(c: dict) -> None:
    import jax
    import optax

    from chipbench import manifest as mf, phases, tracing

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
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh=mesh, rules=rules)
    batch_size, seq = tcfg["global_batch"], traffic["seq_len"]
    make = gen.batch_fn(traffic, cfg.vocab_size, batch_size, seed, sharding)

    def one(i):
        with jax.profiler.TraceAnnotation("chipbench.make_batch"):
            batch = make(i)
        with jax.profiler.TraceAnnotation("chipbench.train_step"):
            new_state, m = step(state_box[0], batch)
        state_box[0] = new_state
        with jax.profiler.TraceAnnotation("chipbench.loss_sync"):
            return float(m["loss"])  # host transfer: the step has run

    state_box = [state]
    del state
    i = 0
    for _ in range(1 + WARM_STEPS):
        # step 0 loads the step from the cache, or compiles it, and runs it first
        with phases.phase("first_step" if i == 0 else "warm_steps"):
            t = time.monotonic()
            loss = one(i)
            session.report({"phase": "warm", "step": i, "loss": loss,
                            "step_s": time.monotonic() - t})
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
        loss = one(i)
        b = clock()
        with jax.profiler.TraceAnnotation("chipbench.report"):
            session.report({"phase": "window", "step": i, "loss": loss,
                            "start": a, "end": b})
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
    mem = None
    if c["trace"]:
        # what the compiler says the step needs, beside memory_stats()'s
        # peak (which PR 21 found leaves the step's temporaries out)
        try:
            ma = step.lower(state_box[0], make(0)).compile().memory_analysis()
            mem = {k: getattr(ma, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")}
        except Exception as e:  # noqa: BLE001 - an earlier line only
            mem = {"error": repr(e)}
    # the peak of the system under test: read before the reference
    # puts its own copy of the parameters on the first chip
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    session.report({"phase": "done", "setup_s": setup_s, "setup_phases": setup_phases,
                    "window_wall": (w0, w1),
                    "memory_analysis": mem, "tokens_per_step": batch_size * seq,
                    "memory_peak_bytes": peak,
                    "platform": jax.devices()[0].platform})
    state_box.clear()


def run(ctx: dict) -> dict:
    import jax

    from chipbench import manifest as mf, phases, tracing
    from chipbench.reference import dense_decoder

    with phases.phase("import_program"):
        import ray_tpu
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    args, config, traffic, chips = ctx["args"], ctx["config"], ctx["traffic"], ctx["chips"]
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
    losses = [m["loss"] for m in hist if "loss" in m]
    tokens_per_step = done["tokens_per_step"]
    span_s = steps[-1]["end"] if steps else 0.0
    train_tok_s = len(steps) * tokens_per_step / span_s if span_s > 0 else None
    ctx["log"](event="train", steps_in_window=len(steps), window_s=span_s,
               step_s=[round(m["end"] - m["start"], 4) for m in steps][:40],
               losses=[round(x, 4) for x in losses][:64],
               memory_analysis=done["memory_analysis"],
               expected=mf.load_plugin(ctx["root"], "generators", traffic["generator"])
               .expected(traffic, config["vocab_size"], args.seed))

    # ---- correct: after the window, on parameters made again from the seed.
    # The step's own first loss is the program's word; the reference says
    # what it should have been, one sequence of batch 0 at a time.
    gc.collect()
    builder = mf.load_plugin(ctx["root"], "model_builders", config["model_builder"])
    gen = mf.load_plugin(ctx["root"], "generators", traffic["generator"])
    cfg, init, _ = builder.build(config)
    params = jax.jit(init)(jax.random.key(args.seed % (2 ** 31)))
    batch = gen.batch_fn(traffic, cfg.vocab_size, config["train"]["global_batch"],
                         args.seed)(0)
    ref = float(dense_decoder.loss(params, batch["tokens"], batch["targets"], config))
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
    ctx["log"](event="correct", checks=checks, first_loss=first, reference_loss=ref,
               rel_err=rel_err, tolerance=LOSS_TOL,
               reference_sequences=int(batch["tokens"].shape[0]))
    run = {
        "kind": "train", "correct": all(checks.values()), "checks": checks,
        "attempted": len(steps), "failed": 0,
        "values": {"train_tok_s": train_tok_s, "setup_s": done["setup_s"]},
        "setup_phases": done["setup_phases"],
        "window_wall": tuple(done["window_wall"]), "seconds": span_s,
        "shape": config, "traffic": traffic, "peaks": ctx["peaks"], "chips": chips,
        "steps": steps, "losses": losses, "tokens_per_step": tokens_per_step,
        "memory_analysis": done["memory_analysis"], "busy": None,
        "memory_peak_bytes": done["memory_peak_bytes"],
    }
    if args.trace:
        run.update(tracing.reduce(os.path.join(ctx["out_dir"], "trace"),
                                  ctx["names"], ctx["log"]))
        run["traced_steps"] = TRACED_STEPS[1] - TRACED_STEPS[0]
    return run
