#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the two hot paths once through the entry points a user calls, at
the full width of `mistral-7b` from the registry (d_model 4096, 32 heads
/ 8 KV heads, head_dim 128, d_ff 14336, vocab 32000). Only depth is cut
(`reduced`), because 32 layers are 29 GB in fp32 and a v5e chip holds
16 GB; weights are random, made from --seed.

Default run (one chip; the phases run as children, one after the other,
of this parent, which never imports JAX — a process that has touched
JAX holds the chip, and every child needs it):

  train    JaxTrainer(...).fit() in the in-process runtime, one worker
           with num_tpus=1, attention_impl="flash".
  serve    the OpenAI app through ray_tpu.serve behind the HTTP proxy,
           POST /v1/completions, checked against llama.forward on the
           same parameters; then the same through mixed_batch=True,
           through attn_impl="pallas" and through both (the Pallas
           ragged kernel in the mixed step). Followed, in the same process,
           by the Pallas paged/ragged kernels against their XLA oracles
           at the serve shapes, and by the expert layer's grouped-matmul
           kernels against jax.lax.ragged_dot at OLMoE-1B-7B's shapes.
  cluster  a one-node LocalCluster with TPU: 1; the trainer's worker is
           a process of its own that must report the TPU, while GCS,
           daemon and CPU workers stay on the CPU.

`--chips 4` (run by hand, never by the driver) runs only the cross-chip
paths and what they are compared with, in one process that drives all
four chips: the tensor-parallel engine, the fsdp x tp train step, and
the one-chip train step on the same seed and batch.

`--phase serve --obs capture|trace` (by hand) repeats the serve phase
with `ray_tpu.obs.capture()` open around the traffic, so its `run_s`
against a plain run is what recording layer spans costs; `trace` also
takes a JAX profiler trace of the default engine's warm pass and reports
the program's spans and program names found in it, and how far the
recorder's clock is from the profiler's.

Every phase prints one JSON line. The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`;
on any failure `"ok": false` and a non-zero exit. Without a TPU no
phase runs. JAX_PLATFORMS is never set here.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time

MODEL = "mistral-7b"
WIDTHS = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "head_dim": 128,
          "d_ff": 14336, "vocab_size": 32000}
TRAIN_LAYERS = 2     # fp32 weights + grads + two Adam moments: 7.8 GiB + 4.8 GiB temp
SERVE_LAYERS = 8     # fp32 weights 7.7 GiB + the step's bf16 copy 3.5-4 GiB
TP4_SERVE_LAYERS = 32  # full depth: 7.0 GiB + 3.4 GiB on each of four chips
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 2, 1024, 5, 3e-4
# two prefill buckets (32, 128) — a cold run is mostly compiling
PROMPT_LENS = (20, 28, 100, 120)
NEW_TOKENS = 12
# bf16 decides the contract (ROADMAP "Notes carried"): the engine and the
# reference round differently (paged fp32-softmax attention vs the bf16
# XLA composite, per-use weight casts), so near-tie argmaxes may differ.
# The returned token must instead be a maximum of the REFERENCE up to
# rounding: logits here have std ~1 and |max| ~4.5, one bf16 ulp at that
# magnitude is 2^-5 = 0.031, and a few such roundings accumulate through
# the residual stream. A wrong position, page or mask moves the token to
# a random logit, ~4.5 below the maximum. Worst gap seen on the chip over
# PR 21's runs: 0.040 (mixed engine); four ulps leave room for other seeds
# and batch compositions, and fp8-grade arithmetic would still fail.
LOGIT_TOL = 0.125
# kernel vs XLA oracle on bf16 outputs of magnitude < 4: two ulps
KERNEL_TOL = 2 * 2.0 ** -6
PALLAS = "pallas"  # the compiled kernel; never the interpreter
# the expert layer's grouped matmuls (ops/grouped_matmul.py) at OLMoE-1B-7B's
# widths: 6 x 4096 tokens x 8 experts each, 64 experts, gate/up and down
GMM_ROWS, GMM_EXPERTS, GMM_SHAPES = 196608, 64, ((2048, 1024), (1024, 2048))
# of the largest magnitude: both sides accumulate in float32, in another
# order, and round once to bf16 (one ulp = 2^-8); seen on the chip 0 to 2^-8
GMM_TOL = 2.0 ** -7

NO_TPU_EXIT = 3
TOTAL_BUDGET_S = 1140  # the contract is 1200 s, compilation included
CHILD_TIMEOUT_S = {"train": 360, "serve": 540, "cluster": 360, "multichip": 1140}


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# children: everything below this line runs in a process that owns the chip
# (or, for the cluster phase's driver, in one that must never touch it)
# ---------------------------------------------------------------------------


def count_cache_hits() -> dict:
    """{"n": programs this process has loaded from the persistent
    compile cache instead of compiling, from now on}."""
    import jax

    hits = {"n": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits["n"] += 1

    jax.monitoring.register_event_listener(on_event)
    return hits


def open_chip(phase: str, want_count: int) -> dict:
    """Place the compile cache, open the backend, refuse anything but
    the TPU. Returns the device record every phase line carries."""
    from ray_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    hits = count_cache_hits()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if devs[0].platform != "tpu" or len(devs) != want_count:
        emit({"phase": phase, "ok": False, "device": device,
              "error": f"need {want_count} TPU chip(s), JAX found {device}"})
        sys.exit(NO_TPU_EXIT)
    return {"device": device, "cache_dir": cache_dir, "cache_hits": hits}


def cache_report(chip: dict) -> dict:
    from ray_tpu.utils.compile_cache import count_cache_entries

    return {"cache_entries": count_cache_entries(chip["cache_dir"]),
            "cache_hits": chip["cache_hits"]["n"]}


def memory_per_device() -> list:
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({"bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def smoke_model(n_layers: int, **overrides):
    """The registry's mistral-7b with n_layers cut and nothing else changed."""
    import dataclasses

    from ray_tpu.models.registry import get_model_config

    full = get_model_config(MODEL)
    cfg = dataclasses.replace(full, n_layers=n_layers, **overrides)
    widths = {k: getattr(cfg, k) for k in WIDTHS}
    if widths != WIDTHS:
        raise RuntimeError(f"{MODEL} is not at its published widths: {widths}")
    return cfg, {"model": MODEL, "widths": widths,
                 "reduced": {"n_layers": [full.n_layers, n_layers]}}


def train_loop(config: dict) -> None:
    """train_loop_per_worker: a handful of steps on one fixed seeded
    batch, on one chip or over config["mesh"]. Reports every step."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.train import session
    from ray_tpu.train.step import TrainState, init_sharded_params, make_train_step

    cache_hits = count_cache_hits()
    cfg, _ = smoke_model(config["n_layers"], attention_impl="flash")
    seed = config["seed"]
    mesh = rules = None

    def init():
        return llama.init_params(cfg, jax.random.key(seed))

    if config.get("mesh"):
        from jax.sharding import NamedSharding

        from ray_tpu.parallel.mesh import MeshSpec, make_mesh
        from ray_tpu.parallel.sharding import default_rules

        mesh, rules = make_mesh(MeshSpec(**config["mesh"])), default_rules()
        params = init_sharded_params(init, llama.logical_axes(cfg), mesh, rules)
    else:
        params = init()
    opt = optax.adamw(TRAIN_LR)
    state = TrainState.create(params, opt)
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh=mesh, rules=rules
    )
    tokens = jax.random.randint(
        jax.random.key(seed + 1), (TRAIN_BATCH, TRAIN_SEQ + 1), 0,
        cfg.vocab_size, jnp.int32,
    )
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    if mesh is not None:
        batch = jax.device_put(
            batch, NamedSharding(mesh, rules.spec(("batch", "seq")))
        )
    kernel_in_hlo = "tpu_custom_call" in step.lower(state, batch).as_text()
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])  # host transfer: the step has run
        session.report({
            "step": i, "loss": loss, "step_s": time.perf_counter() - t0,
            "kernel_in_hlo": kernel_in_hlo,
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "pid": os.getpid(), "cache_hits": cache_hits["n"],
            "memory": memory_per_device(),
        })


def fit_train(chips: int, config: dict, name: str) -> dict:
    """JaxTrainer(...).fit() and the checks every train run is held to."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # a gang's chips come back on a drain thread after fit() returns,
    # and a placement group that does not fit right now is infeasible,
    # not queued: wait for the chips before asking for them
    deadline = time.monotonic() + 60
    while ray_tpu.available_resources().get("TPU", 0) < chips:
        if time.monotonic() > deadline:
            raise RuntimeError(f"{chips} TPU chip(s) never became available")
        time.sleep(0.2)
    result = JaxTrainer(
        train_loop,
        train_loop_config=config,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, chips_per_worker=chips),
        run_config=RunConfig(name=name),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"trainer failed: {result.error!r}")
    hist = result.metrics_history
    losses = [m["loss"] for m in hist]
    ln_v = math.log(WIDTHS["vocab_size"])
    checks = {
        "steps_reported": len(losses) == config["steps"],
        "first_loss_near_ln_vocab": 0.3 * ln_v <= losses[0] <= 3.0 * ln_v,
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss_decreased": losses[-1] < losses[0],
        "kernel_in_hlo": bool(hist[-1]["kernel_in_hlo"]),
        "worker_on_tpu": hist[-1]["platform"] == "tpu",
    }
    steady = [m["step_s"] for m in hist[1:]] or [hist[0]["step_s"]]
    run_s = sorted(steady)[len(steady) // 2]
    return {
        "ok": all(checks.values()), "checks": checks, "losses": losses,
        "compile_s": round(max(0.0, hist[0]["step_s"] - run_s), 2),
        "run_s": round(run_s, 4),
        "kernel_in_hlo": checks["kernel_in_hlo"],
        "memory": hist[-1]["memory"],
        "worker": {"pid": hist[-1]["pid"], "platform": hist[-1]["platform"],
                   "kind": hist[-1]["device_kind"],
                   "cache_hits": hist[-1]["cache_hits"]},
    }


def phase_train(args) -> bool:
    chip = open_chip("train", 1)
    import ray_tpu

    ray_tpu.init()  # must find the chip by itself: no num_tpus, no env var
    advertised = ray_tpu.cluster_resources().get("TPU", 0)
    if advertised < 1:
        raise RuntimeError(f"ray_tpu.init() advertised TPU: {advertised}")
    _, model = smoke_model(TRAIN_LAYERS)
    rec = fit_train(
        1,
        {"n_layers": TRAIN_LAYERS, "steps": TRAIN_STEPS, "seed": args.seed},
        "smoke-train",
    )
    ray_tpu.shutdown()
    emit({"phase": "train", **model, **rec,
          "batch": TRAIN_BATCH, "seq": TRAIN_SEQ, "tpus_advertised": advertised,
          "peak_bytes_in_use": rec["memory"][0]["peak_bytes_in_use"],
          **cache_report(chip), "device": chip["device"]})
    return rec["ok"]


class IdTokenizer:
    """Token ids as decimal text, so the HTTP surface carries exact ids
    both ways (the hermetic ByteTokenizer folds ids >= 259 away)."""

    def __init__(self, vocab_size: int):
        self.eos_token_id = vocab_size - 1

    def encode(self, text: str) -> list:
        return [int(t) for t in text.split()]

    def decode(self, ids: list) -> str:
        return " ".join(str(i) for i in ids)


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def trace_digest(trace_dir: str, spans: list) -> dict:
    """What a profiler trace of the serve leg shows of the program: its
    layer spans among the host events, the names on the device's XLA
    Modules line, and the distance between each recorded engine.step's
    start (recorder clock + the clock markers' offset) and the nearest
    engine.step event in the trace."""
    import glob
    import shutil

    import jax

    from ray_tpu import obs

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    host, modules = [], set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith("/device:"):
                if line.name == "XLA Modules":
                    modules.update(e.name.split("(")[0] for e in line.events)
            else:
                host.extend((e.name, e.start_ns * 1e-9) for e in line.events)
    shutil.rmtree(trace_dir, ignore_errors=True)
    by_name: dict = {}
    for name, _ in host:
        if name.startswith(("runner.", "engine.")):
            by_name[name] = by_name.get(name, 0) + 1
    offset = obs.clock_offset(host)
    steps = sorted(t for name, t in host if name == "engine.step")
    errs = sorted(
        min(abs(s.start + offset - t) for t in steps)
        for s in spans if s.name == "engine.step"
    ) if offset is not None and steps else []
    return {"host_spans": by_name, "xla_modules": sorted(modules),
            "clock_offset_s": offset, "spans_compared": len(errs),
            "clock_err_ms_median": 1e3 * errs[len(errs) // 2] if errs else None,
            "clock_err_ms_max": 1e3 * errs[-1] if errs else None}


def serve_variant(name: str, engine_kwargs: dict, cfg, params, ref_logits,
                  seed: int, observe: str = "off") -> dict:
    """Deploy the OpenAI app with this engine, POST the prompts twice
    (cold, then fresh prompts of the same lengths warm), hold every
    returned token to the reference, read /v1/stats. `observe`:
    "capture" keeps obs.capture() open around both passes, "trace" also
    takes a profiler trace of the warm pass."""
    import concurrent.futures as cf
    import contextlib

    import jax
    import numpy as np
    import requests

    from ray_tpu import obs, serve
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.llm.openai_api import LLMConfig, build_openai_app

    port = free_port()
    serve.start(host="127.0.0.1", port=port)
    base = f"http://127.0.0.1:{port}"
    build_openai_app(
        LLMConfig(model_id=MODEL, engine=EngineConfig(model=cfg, **engine_kwargs),
                  tokenizer=IdTokenizer(cfg.vocab_size), params=params, seed=seed),
        name=f"smoke-{name}", route_prefix="/",
    )

    def complete(prompt_ids: list) -> list:
        r = requests.post(f"{base}/v1/completions", timeout=290, json={
            "prompt": " ".join(map(str, prompt_ids)),
            "max_tokens": NEW_TOKENS, "temperature": 0.0,
        })
        r.raise_for_status()
        body = r.json()
        if "error" in body:
            raise RuntimeError(f"completion failed: {body['error']}")
        return [int(t) for t in body["choices"][0]["text"].split()]

    rng = np.random.default_rng(seed)
    worst_gap, n_tokens, pass_s = 0.0, 0, []
    spans_before = obs.layer_counters()
    trace_dir = os.path.join("chiprun_out", "chip_smoke_trace")
    digest = None
    try:
        for warm in (False, True):
            # ids below the tokenizer's EOS (vocab_size - 1)
            prompts = [rng.integers(3, cfg.vocab_size - 1, n).tolist()
                       for n in PROMPT_LENS]
            tracing = observe == "trace" and warm
            if tracing:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level, opts.host_tracer_level = 0, 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            capture = obs.capture() if observe != "off" else contextlib.nullcontext([])
            t0 = time.perf_counter()
            with capture as spans, cf.ThreadPoolExecutor(len(prompts)) as pool:
                outs = list(pool.map(complete, prompts))
            pass_s.append(time.perf_counter() - t0)
            if tracing:
                jax.profiler.stop_trace()
                digest = trace_digest(trace_dir, spans)
            for prompt, out in zip(prompts, outs):
                if not 1 <= len(out) <= NEW_TOKENS:
                    raise RuntimeError(f"{len(out)} tokens returned, want 1..{NEW_TOKENS}")
                logits = ref_logits(prompt + out)
                for j, tok in enumerate(out):
                    row = logits[len(prompt) + j - 1]
                    worst_gap = max(worst_gap, float(row.max() - row[tok]))
                n_tokens += len(out)
        stats = requests.get(f"{base}/v1/stats", timeout=60).json()
    finally:
        serve.shutdown()
    # what this variant added to the process's layer spans: count, busy ms
    trace_row = {
        n: [c["count"] - spans_before.get(n, {"count": 0})["count"],
            round(1e3 * (c["busy_s"] - spans_before.get(n, {"busy_s": 0.0})["busy_s"]), 2)]
        for n, c in sorted(stats["trace"].items()) if n.startswith(("runner.", "engine."))
    }
    kernels = engine_kernels_in_hlo(cfg, params, engine_kwargs)
    checks = {
        "logit_agreement": worst_gap <= LOGIT_TOL,
        "zero_recoveries": stats["engine_recoveries"] == 0,
        "zero_preemptions": stats["num_preemptions"] == 0,
        # the path this variant exists for actually ran
        "path_ran": "mixed" in stats if engine_kwargs.get("mixed_batch")
        else "pipeline" in stats,
        # every attention program of this engine, and only a pallas engine's
        "kernel_iff_pallas": set(kernels.values())
        == {engine_kwargs.get("attn_impl") == PALLAS},
    }
    return {"engine": name, "ok": all(checks.values()), "checks": checks,
            "kernel_in_hlo": kernels,
            "max_logit_gap": round(worst_gap, 4), "tokens_checked": n_tokens,
            "compile_s": round(pass_s[0] - pass_s[1], 2),
            "run_s": round(pass_s[1], 3), "observe": observe,
            "trace": trace_row, "counters": stats["counters"],
            "runner_lock": stats["runner_lock"],
            **({"profile": digest} if digest else {})}


def engine_kernels_in_hlo(cfg, params, engine_kwargs: dict) -> dict:
    """Which of this engine's own attention programs lower to a Pallas
    custom call: the decode step it jits and, with mixed_batch, its
    mixed step, taken from an engine built the way the app builds it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import EngineConfig, LLMEngine

    eng = LLMEngine(EngineConfig(model=cfg, **engine_kwargs), params=params)
    B, T = len(PROMPT_LENS), 256  # the batch served above, one packed-token bucket

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    lowered = {"decode": eng._decode.lower(
        eng.params, i32(B), i32(B), i32(B), i32(B, 16), i32(B), eng.cache, None)}
    if eng._mixed_fn is not None:
        lowered["mixed"] = eng._mixed_fn.lower(
            eng.params, i32(T), i32(T), i32(T), i32(B, 16), i32(B + 1), i32(B),
            eng.cache, None)
    return {k: "tpu_custom_call" in v.as_text() for k, v in lowered.items()}


def make_reference(cfg, params):
    """prompt + returned tokens through llama.forward once (no cache,
    XLA attention) -> fp32 logits [S, V] on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    # one padded width every prompt + NEW_TOKENS fits: one compile
    width = 1 << (max(PROMPT_LENS) + NEW_TOKENS - 1).bit_length()
    fwd = jax.jit(lambda p, t: llama.forward(p, t, cfg)[0].astype(jnp.float32))

    def ref_logits(ids: list):
        tokens = np.zeros((1, width), np.int32)
        tokens[0, : len(ids)] = ids  # causal: the pad tail changes nothing
        return np.asarray(fwd(params, jnp.asarray(tokens)))

    return ref_logits


def kernel_checks(cfg, block_size: int, num_blocks: int, seed: int) -> dict:
    """paged_attention_pallas and ragged_attention_pallas against their
    XLA oracles at the serve phase's shapes (decode-only and mixed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.paged_attention import paged_attention
    from ray_tpu.ops.ragged import ragged_attention

    H, KVH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    slots = num_blocks * block_size + block_size  # + the trash page
    kc = jnp.asarray(rng.normal(size=(KVH, slots, D)), cfg.dtype)
    vc = jnp.asarray(rng.normal(size=(KVH, slots, D)), cfg.dtype)
    B, MB = len(PROMPT_LENS), 16
    bt = jnp.asarray(rng.permutation(num_blocks)[: B * MB].reshape(B, MB), jnp.int32)
    errs, kernel_in_hlo = {}, True

    def compare(name, rows, fn, *a):
        """max |xla - pallas| over the first `rows` query rows (the rest
        of a packed batch is bucket padding nobody reads)."""
        nonlocal kernel_in_hlo
        outs = {}
        for impl in ("xla", PALLAS):
            f = jax.jit(lambda *x, _i=impl: fn(*x, impl=_i))
            if impl == PALLAS:
                kernel_in_hlo &= "tpu_custom_call" in f.lower(*a).as_text()
            outs[impl] = np.asarray(f(*a).astype(jnp.float32))[:rows]
        errs[name] = float(np.abs(outs["xla"] - outs[PALLAS]).max())

    # decode-only: one row per sequence, contexts = prompt + a few tokens
    ctx = jnp.asarray([n + 5 for n in PROMPT_LENS], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), cfg.dtype)
    compare("paged_decode", B,
            lambda q, kc, vc, bt, ctx, impl: paged_attention(
                q, kc, vc, bt, ctx, block_size=block_size, impl=impl),
            q, kc, vc, bt, ctx)
    cu = jnp.arange(B + 1, dtype=jnp.int32)
    compare("ragged_decode", B,
            lambda q, kc, vc, bt, cu, ctx, impl: ragged_attention(
                q, kc, vc, bt, cu, ctx, block_size=block_size, max_q_len=1, impl=impl),
            q, kc, vc, bt, cu, ctx)
    # mixed: two prompts mid-prefill beside two decode rows, packed
    q_lens = [PROMPT_LENS[2], 1, PROMPT_LENS[1], 1]
    ctx = jnp.asarray([PROMPT_LENS[2], 33, PROMPT_LENS[1], 125], jnp.int32)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    q = jnp.asarray(rng.normal(size=(256, H, D)), cfg.dtype)  # T_pad bucket
    compare("ragged_mixed", sum(q_lens),
            lambda q, kc, vc, bt, cu, ctx, impl: ragged_attention(
                q, kc, vc, bt, cu, ctx, block_size=block_size, max_q_len=256, impl=impl),
            q, kc, vc, bt, cu, ctx)
    ok = kernel_in_hlo and all(
        math.isfinite(e) and e <= KERNEL_TOL for e in errs.values()
    )
    return {"ok": ok, "max_abs_err": errs, "tolerance": KERNEL_TOL,
            "kernel_in_hlo": kernel_in_hlo,
            "shapes": {"heads": H, "kv_heads": KVH, "head_dim": D,
                       "block_size": block_size, "dtype": str(jnp.dtype(cfg.dtype))}}


def grouped_matmul_check(seed: int) -> dict:
    """ops/grouped_matmul.py as a call site gets it (the Pallas kernels,
    on a chip with no mesh) against jax.lax.ragged_dot: value and both
    gradients at the shapes of OLMoE-1B-7B's expert layer in a step of
    6 x 4096 tokens (196,608 rows, 64 experts, 2048 x 1024 and back),
    groups uneven and one of them empty."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.grouped_matmul import grouped_matmul

    P, E = GMM_ROWS, GMM_EXPERTS
    rng = np.random.default_rng(seed)
    share = rng.pareto(1.5, E) + 0.2
    share[rng.integers(E)] = 0.0
    sizes = np.floor(share / share.sum() * P).astype(np.int64)
    sizes[np.argmax(sizes)] += P - sizes.sum()
    group_sizes = jnp.asarray(sizes, jnp.int32)
    errs, kernel_in_hlo = {}, True
    for K, N in GMM_SHAPES:
        keys = jax.random.split(jax.random.key(seed + K), 3)
        lhs = jax.random.normal(keys[0], (P, K), jnp.bfloat16)
        rhs = (jax.random.normal(keys[1], (E, K, N)) / math.sqrt(K)).astype(jnp.bfloat16)
        ct = jax.random.normal(keys[2], (P, N), jnp.bfloat16)
        outs = {}
        for name, fn in (("ragged_dot", jax.lax.ragged_dot), ("kernel", grouped_matmul)):
            f = jax.jit(lambda a, b, c, _fn=fn: jax.vjp(
                lambda x, w: _fn(x, w, group_sizes), a, b)[1](c) + (_fn(a, b, group_sizes),))
            if name == "kernel":
                hlo = f.lower(lhs, rhs, ct).as_text()
                kernel_in_hlo &= "tpu_custom_call" in hlo and "ragged_dot" not in hlo
            outs[name] = [np.asarray(x.astype(jnp.float32)) for x in f(lhs, rhs, ct)]
        for part, want, got in zip(("d_lhs", "d_rhs", "value"), outs["ragged_dot"], outs["kernel"]):
            errs[f"{part}_{K}x{N}"] = float(np.abs(got - want).max() / np.abs(want).max())
    ok = kernel_in_hlo and all(math.isfinite(e) and e <= GMM_TOL for e in errs.values())
    return {"ok": ok, "max_rel_err": errs, "tolerance": GMM_TOL, "kernel_in_hlo": kernel_in_hlo,
            "shapes": {"rows": P, "experts": E, "k_n": GMM_SHAPES, "dtype": "bfloat16",
                       "largest_over_mean_group": float(sizes.max() / sizes.mean())}}


def phase_serve(args) -> bool:
    chip = open_chip("serve", 1)
    import jax

    import ray_tpu
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models import llama

    ray_tpu.init()
    cfg, model = smoke_model(SERVE_LAYERS)
    params = llama.init_params(cfg, jax.random.key(args.seed))
    ref_logits = make_reference(cfg, params)
    ok = True
    # EngineConfig defaults = the pipelined decode path; then the mixed
    # ragged dispatch; then the Pallas paged kernel in the decode chunk;
    # then both, which puts the Pallas ragged kernel in the mixed step
    for name, kw in (("default", {}), ("mixed", {"mixed_batch": True}),
                     ("pallas", {"attn_impl": PALLAS}),
                     ("mixed_pallas", {"mixed_batch": True, "attn_impl": PALLAS})):
        rec = serve_variant(name, kw, cfg, params, ref_logits, args.seed,
                            args.obs if name == "default" or args.obs != "trace"
                            else "capture")
        ok &= rec["ok"]
        emit({"phase": "serve", **model, **rec,
              "logit_tolerance": LOGIT_TOL,
              "peak_bytes_in_use": memory_per_device()[0]["peak_bytes_in_use"],
              **cache_report(chip), "device": chip["device"]})
    ray_tpu.shutdown()
    defaults = EngineConfig(model=cfg)
    rec = kernel_checks(cfg, defaults.block_size, defaults.num_blocks, args.seed)
    emit({"phase": "kernels", **rec, **cache_report(chip), "device": chip["device"]})
    del params, ref_logits  # 7.7 GiB: the grouped matmuls bring 3 GiB of their own
    gmm = grouped_matmul_check(args.seed)
    emit({"phase": "grouped_matmul", **gmm, **cache_report(chip), "device": chip["device"]})
    return ok and rec["ok"] and gmm["ok"]


def proc_env(pid: int) -> dict:
    with open(f"/proc/{pid}/environ", "rb") as f:
        items = f.read().split(b"\0")
    return dict(i.decode().split("=", 1) for i in items if b"=" in i)


def cpu_probe() -> dict:
    import jax

    return {"platform": jax.devices()[0].platform, "pid": os.getpid(),
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}


def phase_cluster(args) -> bool:
    """The driver of a one-node cluster. It must not touch JAX: the
    TPU-lease worker is another process and needs the chip."""
    import ray_tpu
    from ray_tpu.cluster import LocalCluster

    # LocalCluster's default declares a node dead after 2 s without a
    # heartbeat (a test setting); a worker opening the chip can stall the
    # host longer than that
    cluster = LocalCluster(node_death_timeout_s=30.0)
    # the parent's SIGTERM (its time limit) must still stop the daemons
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(1))
    try:
        cluster.start()
        node = cluster.add_node({"num_cpus": 4, "TPU": 1}, node_id="chip-node")
        cluster.wait_for_nodes(1)
        ray_tpu.init(address=cluster.address)
        cfg, model = smoke_model(TRAIN_LAYERS)
        rec = fit_train(
            1,
            {"n_layers": TRAIN_LAYERS, "steps": 2, "seed": args.seed},
            "smoke-cluster",
        )
        probe = ray_tpu.get(ray_tpu.remote(cpu_probe).remote(), timeout=120)
        pinned = {
            "gcs": proc_env(cluster.gcs_proc.pid).get("JAX_PLATFORMS"),
            "daemon": proc_env(node.proc.pid).get("JAX_PLATFORMS"),
            "cpu_worker": probe["JAX_PLATFORMS"],
        }
        from jax._src import xla_bridge  # imported by ray_tpu.train, never opened

        checks = {
            **rec["checks"],
            "worker_is_another_process": rec["worker"]["pid"] != os.getpid(),
            # the train child of this run compiled the same step: the
            # worker must find it in the shared compile cache
            "train_step_from_compile_cache": rec["worker"]["cache_hits"] >= 1,
            "control_plane_pinned_to_cpu": set(pinned.values()) == {"cpu"},
            "cpu_worker_on_cpu": probe["platform"] == "cpu",
            "driver_never_opened_a_backend": not xla_bridge.backends_are_initialized(),
        }
        ok = all(checks.values())
        emit({"phase": "cluster", **model, **rec, "ok": ok, "checks": checks,
              "jax_platforms": pinned,
              "device": {"platform": rec["worker"]["platform"],
                         "kind": rec["worker"]["kind"], "count": 1}})
        return ok
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            cluster.shutdown()


def phase_multichip(args) -> bool:
    """--chips 4, one process driving all four chips: the train step
    over fsdp x tp against the one-chip step on the same seed and batch,
    then the engine under tp=4 at full depth, held to the serve check.
    The memory evidence is bytes_in_use at stated points, device by
    device (peak_bytes_in_use never resets, and the one-chip comparison
    fills device 0 by design)."""
    chip = open_chip("multichip", 4)
    import jax

    import ray_tpu
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import default_rules, tree_shardings

    ray_tpu.init()

    def spread(mem: list) -> bool:
        """Every chip holds its share: nothing parked on device 0."""
        used = [m["bytes_in_use"] for m in mem]
        return min(used) > 0 and max(used) <= 1.25 * min(used)

    cfg, model = smoke_model(TRAIN_LAYERS)
    tcfg = {"n_layers": TRAIN_LAYERS, "steps": TRAIN_STEPS, "seed": args.seed}
    mesh_axes = {"fsdp": 2, "tp": 2}
    sharded = fit_train(4, {**tcfg, "mesh": mesh_axes}, "smoke-train-4chip")
    single = fit_train(1, tcfg, "smoke-train-1chip")
    l4, l1 = sharded["losses"][0], single["losses"][0]
    checks = {
        **{f"sharded_{k}": v for k, v in sharded["checks"].items()},
        **{f"one_chip_{k}": v for k, v in single["checks"].items()},
        # bf16 matmul rounding differs between sharded and unsharded
        # tilings: eps(bf16) = 2^-8 puts ~0.5% relative slack on the loss
        "first_loss_matches_one_chip": abs(l4 - l1) <= 5e-3 + 5e-3 * abs(l1),
        "state_spread_over_chips": spread(sharded["memory"]),
    }
    ok = all(checks.values())
    emit({"phase": "train_4chip", **model, "ok": ok, "checks": checks,
          "mesh": mesh_axes, "batch": TRAIN_BATCH, "seq": TRAIN_SEQ,
          "losses": sharded["losses"], "losses_one_chip": single["losses"],
          "compile_s": sharded["compile_s"], "run_s": sharded["run_s"],
          "run_s_one_chip": single["run_s"],
          "kernel_in_hlo": sharded["kernel_in_hlo"],
          "memory_sharded": sharded["memory"], "memory_one_chip": single["memory"],
          **cache_report(chip), "device": chip["device"]})

    cfg, model = smoke_model(TP4_SERVE_LAYERS)
    tp4 = MeshSpec(tp=4)
    params = jax.jit(
        lambda: llama.init_params(cfg, jax.random.key(args.seed)),
        out_shardings=tree_shardings(
            make_mesh(tp4), default_rules(), llama.logical_axes(cfg)),
    )()
    jax.block_until_ready(params)
    mem_params = memory_per_device()
    rec = serve_variant("tp4", {"mesh_spec": tp4}, cfg, params,
                        make_reference(cfg, params), args.seed)
    ray_tpu.shutdown()
    rec["checks"]["params_spread_over_chips"] = spread(mem_params)
    rec["ok"] = all(rec["checks"].values())
    emit({"phase": "serve_tp4", **model, **rec, "logit_tolerance": LOGIT_TOL,
          "mesh": {"tp": 4}, "memory_after_params": mem_params,
          "memory_after_serving": memory_per_device(),
          **cache_report(chip), "device": chip["device"]})
    return ok and rec["ok"]


PHASES = {"train": phase_train, "serve": phase_serve, "cluster": phase_cluster,
          "multichip": phase_multichip}


# ---------------------------------------------------------------------------
# the parent: no JAX in this process, ever
# ---------------------------------------------------------------------------


def run_child(phase: str, seed: int, timeout_s: int) -> tuple[int, list]:
    """One phase in a child of its own; relays its stdout, returns
    (exit code, the JSON records it printed). The child's whole process
    group is stopped when it ends or overruns."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--seed", str(seed)],
        stdout=subprocess.PIPE, text=True, start_new_session=True,
    )
    records = []

    def on_timeout(*_):
        raise TimeoutError

    signal.signal(signal.SIGALRM, on_timeout)
    signal.alarm(timeout_s)
    try:
        for line in proc.stdout:
            print(line, end="", flush=True)
            if line.startswith('{"phase"'):
                records.append(json.loads(line))
        rc = proc.wait()
    except TimeoutError:
        emit({"phase": phase, "ok": False, "error": f"no end after {timeout_s}s"})
        proc.terminate()  # lets the cluster phase stop its daemons
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            pass
        rc = 124
    finally:
        signal.alarm(0)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    return rc, records


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip paths (run by hand on a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--obs", choices=("off", "capture", "trace"), default="off",
                    help="with --phase serve, by hand: what recording layer spans costs")
    args = ap.parse_args()
    if args.phase:  # child
        try:
            code = 0 if PHASES[args.phase](args) else 1
        except SystemExit as e:
            code = e.code
        except BaseException:  # noqa: BLE001 - reported, then the hard exit below
            import traceback

            traceback.print_exc()
            code = 1
        # hard exit: interpreter finalization races the runtime's daemon
        # threads and can abort a child whose phase already passed
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(code)

    t0 = time.monotonic()
    ok, device = True, None
    for phase in (("multichip",) if args.chips == 4 else ("train", "serve", "cluster")):
        left = int(TOTAL_BUDGET_S - (time.monotonic() - t0))
        if left <= 0:
            emit({"phase": phase, "ok": False, "error": "out of time before it started"})
            ok = False
            break
        rc, records = run_child(phase, args.seed, min(CHILD_TIMEOUT_S[phase], left))
        ok &= rc == 0 and bool(records) and all(r.get("ok") for r in records)
        for r in records:
            device = device or r.get("device")
        if rc == NO_TPU_EXIT:
            break  # no accelerator: no phase runs
    ok = ok and device is not None and device["platform"] == "tpu" \
        and device["count"] == args.chips
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
