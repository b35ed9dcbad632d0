"""runners/train_reference.py with its constants read from the
configuration file: `check.scopes` (the named scopes whose device time
a traced run attributes), `check.loss_tol` (the tolerance of the first
loss against the reference) and `check.routing_tol` (below), each
written there with its reason. That runner fixes the first two for
OLMoE and may not be edited by a PR that adds a configuration; its
module is loaded, the constants are set on it, and its `run` runs: the
trainer, the window's arithmetic, the reference and `correct` are that
runner's. The model builder is handed what that runner hands it: the
configuration file and a key.

WHY THE LOOP IS HERE A SECOND TIME (and the runner not as thin as ISSUE
32 hoped). The router's selection bias has to start BALANCED on the
run's own tokens (`model_builders/registry_zaya.py::balanced_bias` says
why and how), and the only place for that is between `jit(init)(key)`
and the first step. That runner's `train_loop` has no seam there. The
ways round it were tried and cost more: the table as a constant of the
init program compiles that program anew for every seed (34 s a run on
the chip; PR 31 took the same fault out of the batch maker), a host
callback keeps a program out of the compile cache, and nothing but the
key, the batch and the state is an argument of any program that runner
runs. So `train_loop` below IS that runner's, line for line, with the
three lines marked `# balanced` added, and is put in its place for the
length of the run. A `benchmark` PR that gives that loop a seam after
its init (PERF.md section 7 item 4) deletes this copy.

Two more lines, marked `# steady`, stand before the window: everything
alive is collected once and then kept out of the collector's sight
(`gc.freeze`). A process with JAX and the runtime loaded holds so many
objects that ONE full collection takes 68-78 ms (read on the chip, PR
32, call 17: six in a run), and when Python chooses to make one inside
the window, half a step is lost: 0.5-1% of `train_tok_s` in one run of
three here, where each step's report brings the router's tables (the
dense cells report a loss: one window in 25). What the window's own
steps allocate is still collected, in microseconds.

A SECOND READING for `correct`. With one expert a token, a router
logit that the bf16 stream moves past its neighbour swaps a token's
whole expert, and the first loss lands up to 3.4e-4 from the
reference: as near as the reference computed in bfloat16 throughout
lands by luck (its loss is one bfloat16 number). So the timed step's
own FIRST routing is held to the reference's too: of all (layer,
token) pairs, the share whose expert MOVED, counted from the two
`tokens_per_expert` tables (half the sum of their differences: a
lower bound), within `check.routing_tol`. A router that computes in
float32 moves 2 in a thousand; one in bfloat16 cannot tell its experts
apart and moves one in four. The reference module's `loss_parts` gives
the loss and the table in one pass; `loss` is wrapped for the length of
the run to keep them, and to give the reference the step's own
selection bias.

What a share of the experts adds to the report: each step's router
summary also carries `pairs_elsewhere` (a list over the layers), the
log gains the share of the window's pairs that were routed elsewhere,
and the scopes read from the compiled step are split by their prefix:
`run["scopes"]` keeps the `moe.*` ones, which is what the expert
layer's readers sum, and `run["cca_scopes"]` holds the rest."""

from __future__ import annotations

import gc
import importlib
import os
import time

import numpy as np

from chipbench import manifest as mf

_BASE = None  # runners/train_reference.py's module, for the length of a run
_BIAS: list = []  # the balanced selection bias the loop started from


def train_loop(c: dict) -> None:
    """runners/train_reference.py::train_loop with the three lines marked
    `# balanced` and the two marked `# steady` (the module's docstring
    says why it is copied, and why each)."""
    router_summary, SCOPES = _BASE.router_summary, _BASE.SCOPES
    WARM_STEPS, TRACED_STEPS = _BASE.WARM_STEPS, _BASE.TRACED_STEPS
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
        balance = gen.batch_fn(traffic, cfg.vocab_size, tcfg["global_batch"], seed, sharding)  # balanced
        _BIAS[:] = [builder.balanced_bias(cfg, params, balance)]  # balanced
        params["layers"]["router_bias"] = jax.numpy.asarray(_BIAS[0], cfg.param_dtype)  # balanced
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
        gc.collect()  # steady
        gc.freeze()  # steady
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
    global _BASE
    _BASE = base = mf.load_plugin(ctx["root"], "runners", "train_reference")
    check = ctx["config"]["check"]
    base.train_loop = train_loop
    base.LOSS_TOL = float(check["loss_tol"])
    base.SCOPES = tuple(check["scopes"])
    summary, counts, elsewhere = base.router_summary, [], []

    def router_summary(stats):
        out = summary(stats)
        if out is None:
            return None
        if "pairs_elsewhere" in stats:
            out["pairs_elsewhere"] = [int(x) for x in stats["pairs_elsewhere"]]
            elsewhere.append(sum(out["pairs_elsewhere"]) / sum(out["pairs"]))
        if not counts:  # the first step's; the trainer's worker is a thread of this process
            counts.append(np.asarray(stats["tokens_per_expert"], np.int64))
        return out

    base.router_summary = router_summary
    reference = importlib.import_module(f"chipbench.reference.{ctx['config']['reference']}")
    plain_loss, parts = reference.loss, {}

    def loss(params, *args):
        # the reference starts from the selection bias the step started from
        layers = {**params["layers"], "router_bias": np.asarray(_BIAS[0])} if _BIAS else params["layers"]
        parts.update(reference.loss_parts({**params, "layers": layers}, *args))
        return parts["loss"]

    reference.loss = loss
    try:
        got = base.run(ctx)
    finally:
        reference.loss = plain_loss
    ref = np.asarray(parts["tokens_per_expert"], np.int64)
    moved = int(np.abs(counts[0] - ref).sum()) // 2
    share, tol = moved / int(ref.sum()), float(check["routing_tol"])
    got["checks"]["first_routing_is_the_reference"] = share <= tol
    got["correct"] = all(got["checks"].values())
    ctx["log"](event="correct.routing", ok=share <= tol, moved=moved, pairs=int(ref.sum()),
               moved_share=share, tolerance=tol, reference_tokens_per_expert=ref.tolist())
    if elsewhere:  # over the warm steps and the window's
        ctx["log"](event="routing", elsewhere_share_first=elsewhere[0],
                   elsewhere_share_mean=sum(elsewhere) / len(elsewhere),
                   elsewhere_share_last=elsewhere[-1],
                   router_bias=np.asarray(_BIAS[0]).round(5).tolist() if _BIAS else None)
    scopes = got.get("scopes") or {}
    got["scopes"] = {k: v for k, v in scopes.items() if v.startswith("moe.")} or None
    got["cca_scopes"] = {k: v for k, v in scopes.items() if not v.startswith("moe.")} or None
    return got
