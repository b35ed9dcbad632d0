"""LLM serving throughput on the local accelerator.

Continuous-batching decode throughput (tokens/s) for the paged-KV
engine at a fixed concurrency. Prints one JSON line.

--mixed runs the SPLIT-vs-MIXED dispatch A/B: the same decode-heavy
workload with long prefills arriving mid-flight is served by a split
engine (separate prefill and decode programs — every admission stalls
the decode batch behind a bucket-padded prefill) and a mixed engine
(EngineConfig(mixed_batch=True): ONE ragged dispatch per step serves
prompt chunks AND every decode row, ops/ragged.py). Reports tok/s,
decode TPOT p99, padding-waste ratio, and greedy token identity
(bitwise — the split path is the identity oracle); writes
benchmarks/MIXED_serving_r24.json (tier-1 gates mixed tok/s >= split
and token_identical on the checked-in capture).

--pipeline runs the sync-vs-pipelined decode A/B instead
(ray_tpu.llm.pipeline: device-resident batch state, on-device stop
masks, double-buffered dispatch, adaptive chunks): tok/s + TTFT/TPOT
p99 per mode, greedy token identity, host-overlap ratio and chunk-size
distribution; writes benchmarks/PIPELINE_decode_r16.json (tier-1 gates
pipelined tok/s >= sync on the checked-in capture).

--spec runs the SPECULATIVE-decoding benchmark instead: a tiny model is
briefly overfit on repetitive text (so greedy generation actually
continues patterns — acceptance against a random-weight model would
measure nothing), then the same prompts are decoded by a baseline
engine and a prompt-lookup spec engine. Reports tokens/s for both,
token identity (greedy spec must be lossless), and the acceptance-rate
stats from engine.stats(); writes benchmarks/SPEC_decode_r07.json.

--trace additionally writes the per-REQUEST latency breakdown from the
ray_tpu.obs flight recorder (queue_wait / prefill / decode-chunk phase
distributions, TTFT/TPOT/queue/e2e SLO percentiles, span-coverage
honesty) to benchmarks/TRACE_serving_r08.json: where did request X's
wall-clock go.

--disagg runs the MIXED-LOAD prefill-interference benchmark: a fixed
decode-heavy workload is timed twice per serving mode — idle, then with
a feeder hammering long prefills — for (a) one colocated engine and
(b) a disaggregated prefill/decode pair (ray_tpu.llm.disagg). The
number that matters is decode TPOT p99 degradation (mixed / idle) per
mode: disaggregation should hold decode steady where colocated
time-slices. Also records kv-transfer counts/bytes and the e2e
span-coverage of the disagg traces (llm.kv_transfer spans must keep the
>=90% gate). Writes benchmarks/DISAGG_serving_r10.json.

--chaos runs the AVAILABILITY SLO benchmark: the engine serves a fixed
workload under a seeded PREEMPT_ENGINE schedule (the r09 recovery
ladder re-enqueues in-flight requests); reports completion rate plus
client-side TTFT/e2e p99 with and without injection. Writes
benchmarks/CHAOS_serving_r10.json.
"""

from __future__ import annotations

import argparse
import json
import os as _os
import time

_MIXED_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "MIXED_serving_r24.json"
)
_PIPELINE_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "PIPELINE_decode_r16.json"
)
_SPEC_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "SPEC_decode_r07.json"
)
_TRACE_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "TRACE_serving_r08.json"
)
_DISAGG_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "DISAGG_serving_r10.json"
)
_CHAOS_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "CHAOS_serving_r10.json"
)
_KVTIER_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "KVTIER_cache_r17.json"
)
_KVFETCH_OUT = _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "KVFETCH_cache_r18.json"
)


def _write_capture(path: str, payload: dict) -> None:
    """Capture-ledger discipline (obs.perfwatch): every capture ships
    inside the envelope — fingerprint + tolerance bands — so
    scripts/check_perf.py can gate future runs against it."""
    from ray_tpu.obs.perfwatch import save_capture

    save_capture(path, payload)


def _dist(vals: list) -> dict:
    vals = sorted(float(v) for v in vals)
    if not vals:
        return {}

    def pct(p):
        return vals[min(len(vals) - 1, int(len(vals) * p))]

    return {
        "n": len(vals),
        "mean": round(sum(vals) / len(vals), 4),
        "p50": round(pct(0.5), 4),
        "p95": round(pct(0.95), 4),
        "max": round(vals[-1], 4),
        "total": round(sum(vals), 3),
    }


def build_trace_report(recorder) -> dict:
    """Per-phase latency breakdown from the flight recorder: where did
    the benchmark's requests spend their wall-clock (queue_wait /
    prefill / decode chunks / spec rounds), per-request SLOs
    (TTFT/TPOT/queue/e2e distributions), and span-coverage honesty."""
    phases: dict[str, list] = {}
    slos: dict[str, list] = {}
    coverages = []
    n_requests = 0
    for meta in recorder.traces(limit=100_000):
        summary = recorder.summary(meta["trace_id"])
        if summary is None:
            continue
        for span in recorder.get(meta["trace_id"]):
            if span.name.startswith("engine.") and span.name != "engine.preempt":
                phases.setdefault(span.name, []).append(span.duration_s * 1e3)
        attrs = summary.get("attrs", {})
        if "e2e_s" in attrs:  # a finished llm.request root
            n_requests += 1
            coverages.append(summary["coverage_pct"])
            for key in ("ttft_s", "tpot_s", "queue_wait_s", "e2e_s"):
                if key in attrs:
                    slos.setdefault(key, []).append(attrs[key])
    return {
        "requests": n_requests,
        "phases_ms": {k: _dist(v) for k, v in sorted(phases.items())},
        "slo_s": {k: _dist(v) for k, v in sorted(slos.items())},
        "coverage_pct_mean": (
            round(sum(coverages) / len(coverages), 2) if coverages else 0.0
        ),
        "dropped_traces": recorder.num_dropped_traces,
        "dropped_spans": recorder.num_dropped_spans,
    }


def run_spec_bench(args) -> dict:
    """Spec-vs-baseline decode on repetitive prompts. CPU-safe (the
    tier-1 smoke test runs it under JAX_PLATFORMS=cpu)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.llm.spec import SpecConfig
    from ray_tpu.models import llama
    from ray_tpu.train.step import TrainState, make_train_step

    on_tpu = jax.devices()[0].platform == "tpu"
    smoke = bool(_os.environ.get("RAY_TPU_SPEC_SMOKE")) or not on_tpu
    cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    n_requests = 4 if smoke else 16
    max_new = 32 if smoke else 128
    train_steps = int(
        _os.environ.get("RAY_TPU_SPEC_TRAIN_STEPS", 80 if smoke else 200)
    )
    k = args.spec_k

    # teach the model to continue short repeated patterns: acceptance
    # length then measures real drafter/verifier agreement, not noise
    rng = np.random.default_rng(0)
    B, S = 8, 64

    def make_seq():
        p = rng.integers(3, 120, size=rng.integers(4, 9)).tolist()
        return (p * (S // len(p) + 2))[: S + 1]

    params = llama.init_params(cfg, jax.random.key(0))
    opt = optax.adamw(1e-2)
    state = TrainState.create(params, opt)
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt)
    t_train = time.perf_counter()
    for _ in range(train_steps):
        toks = np.asarray([make_seq() for _ in range(B)], np.int32)
        state, m = step(state, {"tokens": jnp.asarray(toks[:, :-1]),
                                "targets": jnp.asarray(toks[:, 1:])})
    final_loss = float(m["loss"])
    t_train = time.perf_counter() - t_train

    prompts = []
    for _ in range(n_requests):
        p = rng.integers(3, 120, size=rng.integers(4, 9)).tolist()
        prompts.append((p * 8)[:32])
    sp = SamplingParams(max_tokens=max_new, temperature=0.0, ignore_eos=True)

    def engine_cfg(spec=None):
        return EngineConfig(
            model=cfg, num_blocks=512, block_size=8,
            max_num_seqs=min(n_requests, 16), max_prefill_len=64, spec=spec,
        )

    def timed_generate(engine):
        # warmup compiles every shape, then a steady-state timed pass
        engine.generate(prompts[: max(2, n_requests // 2)], sp)
        t0 = time.perf_counter()
        outs = engine.generate(prompts, sp)
        dt = time.perf_counter() - t0
        return outs, sum(len(o) for o in outs), dt

    base = LLMEngine(engine_cfg(), params=state.params, seed=0)
    base_out, base_toks, base_dt = timed_generate(base)

    spec_cfg = SpecConfig(num_draft_tokens=k, method="prompt_lookup")
    eng = LLMEngine(engine_cfg(spec_cfg), params=state.params, seed=0)
    spec_out, spec_toks, spec_dt = timed_generate(eng)

    stats = eng.stats()["spec"]
    result = {
        "metric": "llm_spec_decode_tok_s" if on_tpu else "llm_spec_smoke_tok_s",
        "value": round(spec_toks / spec_dt, 1),
        "unit": "tok/s",
        "vs_baseline": round((spec_toks / spec_dt) / (base_toks / base_dt), 3),
        "baseline_tok_s": round(base_toks / base_dt, 1),
        "token_identical": spec_out == base_out,
        "num_draft_tokens": k,
        "mean_accepted_len": stats["mean_accepted_len"],
        "acceptance_rate": stats["acceptance_rate"],
        "spec_steps": stats["steps"],
        "drafted_tokens": stats["drafted_tokens"],
        "accepted_tokens": stats["accepted_tokens"],
        "n_requests": n_requests,
        "max_new": max_new,
        "train_steps": train_steps,
        "train_s": round(t_train, 2),
        "final_train_loss": round(final_loss, 3),
        "model_params": cfg.num_params(),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }
    if not result["token_identical"]:
        result["warning"] = "greedy spec output diverged from baseline"
    if not on_tpu:
        # at tiny-model CPU scale the decode step is dispatch-dominated,
        # not HBM-bandwidth-dominated, so the tokens/s ratio is noise;
        # mean_accepted_len / acceptance_rate are the deterministic
        # signals a CPU capture carries
        result["note"] = (
            "CPU smoke: vs_baseline wall-clock is dispatch-bound noise; "
            "acceptance stats are the capture's contract"
        )
    _write_capture(args.spec_out, result)
    result["spec_out"] = args.spec_out
    return result


# ---------------------------------------------------------------------------
# --disagg: mixed-load prefill-interference benchmark
# ---------------------------------------------------------------------------


def _pct(vals: list, p: float) -> float:
    vals = sorted(float(v) for v in vals)
    if not vals:
        return 0.0
    return vals[min(len(vals) - 1, int(len(vals) * p))]


def _drive_decode_workload(submit, prompts, sp, timeout_s: float = 300.0):
    """Submit `prompts` through `submit(prompt, sp) -> (rid, queue)` and
    stamp client-side arrival times: per-request ttft / tpot / e2e.
    Consumption is one thread per request so a slow consumer can never
    skew another request's timestamps."""
    import queue as _q
    import threading

    records = []

    def consume(q, rec):
        deadline = time.perf_counter() + timeout_s
        while True:
            try:
                out = q.get(timeout=max(0.01, deadline - time.perf_counter()))
            except _q.Empty:
                rec["error"] = "timeout"
                return
            now = time.perf_counter()
            if out is None:
                return
            if isinstance(out, BaseException):
                rec["error"] = repr(out)
                return
            if out.new_token_ids and "t_first" not in rec:
                rec["t_first"] = now
            if out.finished:
                rec["t_last"] = now
                rec["n"] = len(out.output_token_ids)
                return

    threads = []
    for p in prompts:
        rec = {"t_submit": time.perf_counter()}
        rid, q = submit(p, sp)
        records.append(rec)
        t = threading.Thread(target=consume, args=(q, rec), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=timeout_s)
    ttfts, tpots, e2es, errors = [], [], [], 0
    for rec in records:
        if "error" in rec or "t_last" not in rec:
            errors += 1
            continue
        ttfts.append(rec["t_first"] - rec["t_submit"])
        e2es.append(rec["t_last"] - rec["t_submit"])
        if rec["n"] > 1:
            tpots.append((rec["t_last"] - rec["t_first"]) / (rec["n"] - 1))
    return {
        "completed": len(records) - errors,
        "submitted": len(records),
        "ttft_p99_s": round(_pct(ttfts, 0.99), 5),
        "tpot_p50_s": round(_pct(tpots, 0.50), 5),
        "tpot_p99_s": round(_pct(tpots, 0.99), 5),
        "e2e_p99_s": round(_pct(e2es, 0.99), 5),
    }


def run_disagg_bench(args) -> dict:
    """Decode TPOT under concurrent long prefills: colocated engine vs
    disaggregated prefill/decode pools, each against its own idle
    baseline. CPU-safe (the tier-1 smoke runs it under JAX_PLATFORMS=cpu)."""
    import dataclasses
    import queue as _q
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.disagg import DisaggConfig, DisaggOrchestrator
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.openai_api import _EngineRunner
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import llama
    from ray_tpu.obs import get_recorder

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = llama.LLAMA_400M
        n_short, short_len, max_new = 16, 64, 96
        long_len, num_blocks, max_prefill = 960, 2048, 1024
        n_feeders = 4
    else:
        cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
        n_short, short_len, max_new = 8, 12, 24
        long_len, num_blocks, max_prefill = 90, 256, 96
        n_feeders = 2
    ec = EngineConfig(
        model=cfg, num_blocks=num_blocks, block_size=8,
        max_num_seqs=n_short + n_feeders, max_prefill_len=max_prefill,
        decode_chunk=4,
    )
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    shorts = [
        [int(x) for x in rng.integers(3, cfg.vocab_size - 1, short_len)]
        for _ in range(n_short)
    ]
    sp = SamplingParams(max_tokens=max_new, temperature=0.0, ignore_eos=True)
    sp_long = SamplingParams(max_tokens=1, temperature=0.0, ignore_eos=True)

    def fresh_long():
        # UNIQUE every time: a repeated long prompt would prefix-cache-hit
        # and the "long prefill" would stop costing anything
        return [int(x) for x in rng.integers(3, cfg.vocab_size - 1, long_len)]

    def run_mode(submit, label: str) -> dict:
        # warmup compiles every shape the timed phases will hit: the FULL
        # short batch (decode bucket = n_short) and the long-prefill
        # bucket — an under-warmed idle phase would bill compilation to
        # TPOT and fake a "mixed is faster" inversion
        _drive_decode_workload(submit, shorts, sp)
        _drive_decode_workload(submit, [fresh_long()], sp_long)
        idle = _drive_decode_workload(submit, shorts, sp)
        # mixed: feeders hammer long prefills for the whole window
        stop = threading.Event()

        def feeder():
            while not stop.is_set():
                _rid, q = submit(fresh_long(), sp_long)
                deadline = time.perf_counter() + 60
                while not stop.is_set() and time.perf_counter() < deadline:
                    try:
                        out = q.get(timeout=0.25)
                    except _q.Empty:
                        continue
                    if out is None or isinstance(out, BaseException) or out.finished:
                        break

        feeders = [threading.Thread(target=feeder, daemon=True)
                   for _ in range(n_feeders)]
        for f in feeders:
            f.start()
        time.sleep(0.2)  # let prefill pressure build before measuring
        mixed = _drive_decode_workload(submit, shorts, sp)
        stop.set()
        for f in feeders:
            f.join(timeout=10)
        degradation = (
            round(mixed["tpot_p99_s"] / idle["tpot_p99_s"], 3)
            if idle["tpot_p99_s"] > 0 else None
        )
        return {"idle": idle, "mixed": mixed,
                "tpot_p99_degradation": degradation}

    # colocated: one engine, the r09 runner loop
    engine = LLMEngine(ec, params=params, seed=0)
    runner = _EngineRunner(engine)
    colocated = run_mode(lambda p, s: runner.submit(p, s), "colocated")
    runner.shutdown()

    # disaggregated: 1 prefill + 1 decode pool over the in-proc connector
    orch = DisaggOrchestrator(
        DisaggConfig(engine=ec, num_prefill=1, num_decode=1,
                     connector=args.disagg_connector),
        params=params, seed=0, model_tag="disagg-bench",
    )
    rec = get_recorder()
    rec.clear()  # coverage describes the disagg phases only
    disagg = run_mode(lambda p, s: orch.submit(p, s), "disagg")
    coverages, kv_spans = [], 0
    for meta in rec.traces(limit=100_000):
        summary = rec.summary(meta["trace_id"])
        if summary is None:
            continue
        if "e2e_s" in summary.get("attrs", {}):
            coverages.append(summary["coverage_pct"])
        kv_spans += sum(
            1 for s_ in rec.get(meta["trace_id"]) if s_.name == "llm.kv_transfer"
        )
    tstats = orch.stats()["transfer"]
    orch.shutdown()

    result = {
        "metric": "llm_disagg_tpot_guard" if on_tpu else
        "llm_disagg_tpot_guard_smoke",
        # the headline: how much less decode degrades under prefill load
        "value": (
            round(colocated["tpot_p99_degradation"]
                  / disagg["tpot_p99_degradation"], 3)
            if disagg["tpot_p99_degradation"] else None
        ),
        "unit": "colocated_degradation / disagg_degradation (>1 = disagg wins)",
        "colocated": colocated,
        "disagg": disagg,
        "kv_transfers": tstats["kv_transfers"],
        "kv_bytes": tstats["bytes_sent"],
        "reprefills": tstats["reprefills"],
        "kv_transfer_spans": kv_spans,
        "coverage_pct_mean": (
            round(sum(coverages) / len(coverages), 2) if coverages else 0.0
        ),
        "connector": args.disagg_connector,
        "n_short": n_short, "max_new": max_new, "long_len": long_len,
        "n_feeders": n_feeders,
        "model_params": cfg.num_params(),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }
    if not on_tpu:
        result["note"] = (
            "CPU smoke: absolute TPOT is dispatch-bound; the contract this "
            "capture carries is the RELATIVE degradation (disagg must not "
            "degrade more than colocated) and the >=90% span coverage"
        )
    _write_capture(args.disagg_out, result)
    result["disagg_out"] = args.disagg_out
    return result


# ---------------------------------------------------------------------------
# --pipeline: sync vs pipelined decode A/B
# ---------------------------------------------------------------------------


def _drive_engine_loop(engine, prompts, sp) -> dict:
    """Single-threaded engine.step() loop with client-side per-request
    stamps (TTFT / TPOT / e2e) — the pipelined path's overlap shows up
    here as wall-clock, not just in its own counters."""
    import time as _t

    recs = {}
    t0 = _t.perf_counter()
    for i, p in enumerate(prompts):
        rid = engine.add_request(p, sp, request_id=f"pb-{id(engine)}-{i}")
        recs[rid] = {"order": i}
    generated = 0
    while engine.has_unfinished():
        for o in engine.step():
            now = _t.perf_counter()
            rec = recs[o.request_id]
            if o.new_token_ids and "first" not in rec:
                rec["first"] = now
            if o.finished:
                rec["last"] = now
                rec["n"] = len(o.output_token_ids)
                rec["tokens"] = list(o.output_token_ids)
            generated += len(o.new_token_ids)
    dt = _t.perf_counter() - t0
    ttfts = [r["first"] - t0 for r in recs.values() if "first" in r]
    tpots = [
        (r["last"] - r["first"]) / (r["n"] - 1)
        for r in recs.values() if "last" in r and r.get("n", 0) > 1
    ]
    outs = [r["tokens"] for r in
            sorted(recs.values(), key=lambda r: r["order"]) if "tokens" in r]
    return {
        "tok_s": round(generated / dt, 1),
        "generated_tokens": generated,
        "wall_s": round(dt, 3),
        "ttft_p99_s": round(_pct(ttfts, 0.99), 5),
        "tpot_p50_s": round(_pct(tpots, 0.50), 5),
        "tpot_p99_s": round(_pct(tpots, 0.99), 5),
        "outputs": outs,
    }


def run_pipeline_bench(args) -> dict:
    """Sync vs pipelined decode A/B on the same weights + workload:
    tokens/s, TTFT/TPOT p99, greedy token identity (the correctness
    contract), and the pipelined engine's host-overlap ratio +
    chunk-size distribution. CPU-safe (the tier-1 gate asserts
    pipelined tok/s >= sync on the checked-in capture)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import llama

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = llama.LLAMA_400M
        n_requests, prompt_len, max_new, num_blocks = 16, 128, 128, 1024
    else:
        cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
        n_requests, prompt_len, max_new, num_blocks = 8, 16, 64, 256
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [
        [int(x) for x in rng.integers(3, cfg.vocab_size - 1, prompt_len)]
        for _ in range(n_requests)
    ]
    sp = SamplingParams(max_tokens=max_new, temperature=0.0, ignore_eos=True)

    def build(pipelined: bool) -> LLMEngine:
        return LLMEngine(
            EngineConfig(
                model=cfg, num_blocks=num_blocks, block_size=8,
                max_num_seqs=min(n_requests, 16), max_prefill_len=prompt_len,
                decode_chunk=8, pipeline_decode=pipelined,
            ),
            params=params, seed=0,
        )

    def timed(pipelined: bool):
        engine = build(pipelined)
        _drive_engine_loop(engine, prompts, sp)      # warmup: compile shapes
        out = _drive_engine_loop(engine, prompts, sp)
        return engine, out

    sync_eng, sync = timed(False)
    pipe_eng, pipe = timed(True)
    identical = sync.pop("outputs") == pipe.pop("outputs")
    pipe_row = pipe_eng.stats().get("pipeline", {})

    result = {
        "metric": "llm_pipeline_decode_speedup" if on_tpu
        else "llm_pipeline_decode_speedup_smoke",
        "value": round(pipe["tok_s"] / sync["tok_s"], 3) if sync["tok_s"] else None,
        "unit": "pipelined tok/s over sync tok/s (>= 1 gated in tier-1)",
        "sync": sync,
        "pipelined": pipe,
        "token_identical": identical,
        "pipeline": pipe_row,
        "host_overlap_ratio": pipe_row.get("overlap_ratio"),
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "model_params": cfg.num_params(),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }
    if not identical:
        result["warning"] = "pipelined output diverged from sync baseline"
    if not on_tpu:
        result["note"] = (
            "CPU smoke: host and 'device' share cores, so the overlap "
            "win is mostly the state-residency saving (no per-round "
            "numpy rebuild / key restack) + the all-done early-out; the "
            "TPU capture is where hidden host latency dominates"
        )
    _write_capture(args.pipeline_out, result)
    result["pipeline_out"] = args.pipeline_out
    return result


# ---------------------------------------------------------------------------
# --mixed: split vs mixed ragged dispatch (ray_tpu.llm.mixed)
# ---------------------------------------------------------------------------


def run_mixed_bench(args) -> dict:
    """Split vs MIXED dispatch A/B under the interference load the
    mixed path exists for: a decode-heavy running batch with long
    prefills arriving mid-flight. The split engine serves each arrival
    as its own bucket-padded prefill program (the decode batch stalls
    behind it); the mixed engine packs the prompt chunks and every
    decode row into ONE ragged dispatch per step (ops/ragged.py), so
    decode advances every step. Greedy token identity vs the split
    baseline is the correctness contract; tok/s >= split and
    token_identical are tier-1 gated on the checked-in capture."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import llama

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = llama.LLAMA_400M
        n_decode, n_prefill = 12, 8
        short_len, long_len, max_new = 16, 384, 96
        num_blocks = 1024
    else:
        cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
        n_decode, n_prefill = 10, 10
        short_len, long_len, max_new = 16, 48, 48
        num_blocks = 512
    # per-step prefill budget = the full prompt: each arrival is served
    # by ONE ragged dispatch (T comparable to split's bucket-padded
    # prefill program) with every decode row riding in it for free.
    # Chunking below the prompt length trades per-arrival latency for
    # decode TPOT — tests cover it; the A/B measures the 1:1 swap.
    chunk = long_len
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    shorts = [
        [int(x) for x in rng.integers(3, cfg.vocab_size - 1, short_len)]
        for _ in range(n_decode)
    ]
    longs = [
        [int(x) for x in rng.integers(3, cfg.vocab_size - 1, long_len)]
        for _ in range(n_prefill)
    ]
    sp = SamplingParams(max_tokens=max_new, temperature=0.0, ignore_eos=True)
    sp_long = SamplingParams(max_tokens=max_new // 4, temperature=0.0,
                             ignore_eos=True)

    def build(mixed: bool) -> LLMEngine:
        return LLMEngine(
            EngineConfig(
                model=cfg, num_blocks=num_blocks, block_size=8,
                max_num_seqs=n_decode + n_prefill, max_prefill_len=long_len,
                # one-token-per-round decode on BOTH sides: the A/B
                # isolates the dispatch STRUCTURE (split programs vs one
                # ragged program). Multi-token pipelined chunks are an
                # orthogonal axis (PIPELINE_decode_r16 measures it) and
                # compose with mixed only in decode-only phases.
                decode_chunk=1, pipeline_decode=False, mixed_batch=mixed,
                mixed_prefill_chunk=chunk,
                # the warmup drive replays the same prompts; with prefix
                # caching on, the timed drive's prefills would be cache
                # hits and the A/B would measure nothing.
                enable_prefix_caching=False,
            ),
            params=params, seed=0,
        )

    _drive_seq = [0]

    def drive(engine) -> dict:
        """Decode-heavy load with long prefills arriving MID-flight:
        the short requests enter first; each long prompt arrives after
        a fixed number of engine steps (deterministic — identity must
        not depend on wall-clock). Client-side TPOT stamps cover the
        decode rows the arrivals interfere with."""
        import time as _t

        _drive_seq[0] += 1
        tag = f"mx{id(engine)}-{_drive_seq[0]}"
        recs = {}
        t0 = _t.perf_counter()
        for i, p in enumerate(shorts):
            rid = engine.add_request(p, sp, request_id=f"{tag}-d{i}")
            recs[rid] = {"order": i}
        arrivals = {2 + 2 * j: (j, p) for j, p in enumerate(longs)}
        steps = 0
        generated = 0
        while engine.has_unfinished() or arrivals:
            got = arrivals.pop(steps, None)
            if got is not None:
                j, p = got
                rid = engine.add_request(
                    p, sp_long, request_id=f"{tag}-p{j}"
                )
                recs[rid] = {"order": n_decode + j}
            for o in engine.step():
                now = _t.perf_counter()
                rec = recs[o.request_id]
                if o.new_token_ids and "first" not in rec:
                    rec["first"] = now
                if o.finished:
                    rec["last"] = now
                    rec["n"] = len(o.output_token_ids)
                    rec["tokens"] = list(o.output_token_ids)
                generated += len(o.new_token_ids)
            steps += 1
        dt = _t.perf_counter() - t0
        tpots = [
            (r["last"] - r["first"]) / (r["n"] - 1)
            for r in recs.values() if "last" in r and r.get("n", 0) > 1
        ]
        outs = [r["tokens"] for r in
                sorted(recs.values(), key=lambda r: r["order"])
                if "tokens" in r]
        return {
            "tok_s": round(generated / dt, 1),
            "generated_tokens": generated,
            "wall_s": round(dt, 3),
            "tpot_p99_s": round(_pct(tpots, 0.99), 5),
            "engine_steps": steps,
            "outputs": outs,
        }

    # the CPU smoke's per-arrival margin is a few ms on a shared
    # machine, so a single timed pass is hostage to load drift.
    # INTERLEAVE the A/B (drift hits both sides of a trial equally)
    # and gate on the median per-trial ratio; token identity must hold
    # on every trial, not just one.
    split_eng, mixed_eng = build(False), build(True)
    drive(split_eng)             # warmup: compile every shape
    drive(mixed_eng)
    n_trials = 7
    split_runs, mixed_runs, ratios = [], [], []
    identical = True
    for _ in range(n_trials):
        s_run = drive(split_eng)
        m_run = drive(mixed_eng)
        identical = identical and (s_run["outputs"] == m_run["outputs"])
        split_runs.append(s_run)
        mixed_runs.append(m_run)
        ratios.append(m_run["tok_s"] / s_run["tok_s"]
                      if s_run["tok_s"] else 0.0)
    order = sorted(range(n_trials), key=lambda i: ratios[i])
    mid = order[n_trials // 2]
    split, mixed = split_runs[mid], mixed_runs[mid]
    for r in split_runs + mixed_runs:
        r.pop("outputs")
    mixed_row = mixed_eng.stats().get("mixed", {})

    result = {
        "metric": "llm_mixed_dispatch_speedup" if on_tpu
        else "llm_mixed_dispatch_speedup_smoke",
        "value": round(sorted(ratios)[n_trials // 2], 3),
        "unit": "mixed tok/s over split tok/s, median of "
        f"{n_trials} interleaved trials (>= 1 gated in tier-1)",
        "trial_ratios": [round(r, 3) for r in ratios],
        "split": split,
        "mixed": mixed,
        "token_identical": identical,
        "mixed_stats": mixed_row,
        "padding_waste_ratio": mixed_row.get("padding_waste_ratio"),
        "n_decode": n_decode,
        "n_prefill": n_prefill,
        "long_len": long_len,
        "mixed_prefill_chunk": chunk,
        "model_params": cfg.num_params(),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }
    if not identical:
        result["warning"] = "mixed output diverged from split baseline"
    if not on_tpu:
        result["note"] = (
            "CPU smoke: the mixed win here is fewer total dispatches "
            "(decode rows ride the prefill chunks' program) + no "
            "bucket-padded standalone prefill; the TPU capture is where "
            "the dispatch-gap elimination dominates"
        )
    _write_capture(args.mixed_out, result)
    result["mixed_out"] = args.mixed_out
    return result


# ---------------------------------------------------------------------------
# --chaos: availability SLO under seeded engine preemption
# ---------------------------------------------------------------------------


def run_chaos_bench(args) -> dict:
    """Completion rate + client-side TTFT/e2e p99 under a seeded
    PREEMPT_ENGINE schedule, against an uninjected baseline of the same
    workload (the r09 recovery ladder is what's being priced)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.chaos import harness as chaos
    from ray_tpu.chaos.schedule import FaultSchedule, FaultSpec
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.openai_api import _EngineRunner
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import llama

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = (llama.LLAMA_400M if on_tpu
           else dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32))
    n_requests = 24 if on_tpu else 12
    max_new = 48 if on_tpu else 24
    ec = EngineConfig(
        model=cfg, num_blocks=1024 if on_tpu else 128, block_size=8,
        max_num_seqs=16, max_prefill_len=64, decode_chunk=4,
    )
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [
        [int(x) for x in rng.integers(3, cfg.vocab_size - 1, 16)]
        for _ in range(n_requests)
    ]
    sp = SamplingParams(max_tokens=max_new, temperature=0.0, ignore_eos=True)

    def run_pass():
        engine = LLMEngine(ec, params=params, seed=0)

        def _factory():
            return LLMEngine(ec, params=params, seed=0)

        runner = _EngineRunner(engine, engine_factory=_factory)
        out = _drive_decode_workload(
            lambda p, s: runner.submit(p, s), prompts, sp, timeout_s=180.0
        )
        out["engine_recoveries"] = runner.num_recoveries
        runner.shutdown()
        return out

    baseline = run_pass()

    sched = FaultSchedule(args.chaos_seed, [
        FaultSpec(
            chaos.PREEMPT_ENGINE, site="llm.engine.step",
            p=args.chaos_rate, start_after=4, every_n=3, max_fires=2,
        ),
    ])
    chaos.install(sched)
    try:
        injected = run_pass()
        fired = sched.fired_kinds()
    finally:
        chaos.uninstall()

    result = {
        "metric": "llm_chaos_completion_rate" if on_tpu else
        "llm_chaos_completion_rate_smoke",
        "value": round(injected["completed"] / injected["submitted"], 4),
        "unit": "completed/submitted under seeded preemption",
        "chaos_seed": args.chaos_seed,
        "preempt_rate": args.chaos_rate,
        "faults_fired": len(fired),
        "fired_kinds": fired,
        "baseline": baseline,
        "injected": injected,
        "n_requests": n_requests,
        "max_new": max_new,
        "model_params": cfg.num_params(),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }
    _write_capture(args.chaos_out, result)
    result["chaos_out"] = args.chaos_out
    return result


# ---------------------------------------------------------------------------
# --kvtier: tiered prefix cache on a system-prompt-heavy workload
# ---------------------------------------------------------------------------


def run_kvtier_bench(args) -> dict:
    """Two experiments, one capture:

    1. TIER DEPTH — one engine, a long shared system prefix + distinct
       user suffixes, with filler prompts thrashing the deliberately
       tiny HBM cache between same-prefix requests (the millions-of-
       users shape: the prefix everybody shares never stays resident).
       Per config (HBM-only, +host, +host+object-store) we measure the
       cached-token ratio over the measured requests and client TTFT.
       Resurrection replaces prefix recompute, so hit-rate must rise
       and TTFT must not regress as the ladder deepens.

    2. ROUTING A/B — two engines, three system-prompt families in a
       seeded interleave, host tiers sized so ONE engine cannot hold
       every family. Prefix-aware routing (the orchestrator's
       tier-discounted pick) keeps each family where its KV lives;
       prefix-blind (queue-depth ladder, which ties to engine 0 at
       equal depth) piles every family onto one engine and thrashes.
       The gate is cached-token ratio, aware > blind.
    """
    import numpy as np

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.kvtier import KVTierConfig
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import llama

    BS = 16
    # a model big enough that recomputing the shared prefix actually
    # costs something on CPU (the TTFT comparison must price compute vs
    # resurrection, not jit-dispatch noise): 4 layers, 320-token prefix
    model = llama.LlamaConfig(
        vocab_size=512, d_model=192, n_layers=4, n_heads=6, n_kv_heads=2,
        d_ff=384, max_seq=512, remat=False,
    )
    rng = np.random.RandomState(args.kvtier_seed)
    sys_prefix = list(rng.randint(3, 200, size=20 * BS))  # 320 shared tokens

    def engine_cfg(kvt):
        return EngineConfig(model=model, num_blocks=40, block_size=BS,
                            max_num_seqs=4, max_prefill_len=512, kvtier=kvt)

    def run_once(eng, prompt, sp, rid):
        """(ttft_s, cached_tokens, output_tokens) for one request."""
        t0 = time.perf_counter()
        eng.add_request(prompt, sp, request_id=rid)
        ttft = cached = None
        toks = []
        while eng.has_unfinished():
            for o in eng.step():
                if o.request_id != rid:
                    continue
                if ttft is None and o.new_token_ids:
                    ttft = time.perf_counter() - t0
                    cached = o.num_cached_tokens
                if o.finished:
                    toks = o.output_token_ids
        return ttft, cached or 0, toks

    greedy = SamplingParams(max_tokens=8, temperature=0.0)
    rounds = args.kvtier_rounds

    warmup = 2  # excluded from TTFT/hit stats: jit compiles land here

    def tier_depth_run(kvt) -> dict:
        eng = LLMEngine(engine_cfg(kvt), seed=0)
        ttfts, cached, prompt_toks, token_ids = [], 0, 0, []
        for i in range(rounds + warmup):
            # thrash: distinct fillers evict the shared prefix from HBM
            for j in range(2):
                run_once(eng, list(np.random.RandomState(
                    1000 + i * 7 + j).randint(3, 200, size=24 * BS)),
                    SamplingParams(max_tokens=2, temperature=0.0),
                    f"fill-{i}-{j}")
            sfx = list(np.random.RandomState(i).randint(3, 200, size=BS))
            ttft, c, toks = run_once(eng, sys_prefix + sfx, greedy,
                                     f"req-{i}")
            token_ids.append(toks)
            if i < warmup:
                continue
            ttfts.append(ttft * 1e3)
            cached += c
            prompt_toks += len(sys_prefix) + len(sfx)
        st = eng.stats()
        return {
            "hit_rate": round(cached / prompt_toks, 4),
            "cached_tokens": cached,
            "prompt_tokens": prompt_toks,
            "ttft_ms": _dist(ttfts),
            "ttft_p50_ms": _dist(ttfts)["p50"],
            "by_tier": st["prefix_cache"]["by_tier"],
            "kv_tiers": st.get("kv_tiers"),
            "token_ids": token_ids,
        }

    host_cfg = KVTierConfig(host_bytes=64 << 20, object_bytes=0)
    # deepest ladder: a 1-byte host budget demotes every spill straight
    # to the object store, so hits are served from the deepest tier
    obj_cfg = KVTierConfig(host_bytes=1, object_bytes=256 << 20)
    tiers = {
        "hbm_only": tier_depth_run(None),
        "host": tier_depth_run(host_cfg),
        "host_object": tier_depth_run(obj_cfg),
    }
    # correctness rail: resurrection must not change a single token
    identical = (tiers["host"]["token_ids"] == tiers["hbm_only"]["token_ids"]
                 and tiers["host_object"]["token_ids"]
                 == tiers["hbm_only"]["token_ids"])
    for t in tiers.values():
        del t["token_ids"]

    # -- routing A/B ----------------------------------------------------------
    # the tiny default model (routing is about WHERE, not compute cost),
    # three prompt families on two engines, host tiers sized to ~1.5
    # families so ONE engine cannot hold every family's spilled prefix
    def ab_cfg(kvt):
        return EngineConfig(num_blocks=16, block_size=BS, max_num_seqs=4,
                            max_prefill_len=128, kvtier=kvt)

    ab_block_bytes = 2 * 2 * 2 * BS * 16 * 2  # K+V * L * KVH * bs * D * bf16
    ab_kvt = KVTierConfig(host_bytes=8 * ab_block_bytes, object_bytes=0)
    families = [list(np.random.RandomState(50 + f).randint(3, 200, size=5 * BS))
                for f in range(3)]
    ab_rounds = max(rounds, 8)
    order = [f for _ in range(ab_rounds) for f in range(3)]
    np.random.RandomState(args.kvtier_seed).shuffle(order)

    def routing_run(aware: bool) -> dict:
        engines = [LLMEngine(ab_cfg(ab_kvt), seed=0) for _ in range(2)]
        cached = prompt_toks = 0
        for i, fam in enumerate(order):
            prompt = families[fam] + list(
                np.random.RandomState(i).randint(3, 200, size=BS))
            # both arms break depth ties round-robin (sequential arrivals
            # always tie at depth 0 — p2c at equal depth is a coin flip,
            # modeled deterministically); the aware arm OVERRIDES with
            # the orchestrator's tier-discounted pick when any engine
            # holds the family's prefix
            pick = i % 2
            if aware:
                scores = [e.peek_prefix_tiered(prompt)["discounted"]
                          for e in engines]
                if max(scores) > 0.0:
                    pick = max(range(2), key=lambda k: scores[k])
            _t, c, _toks = run_once(engines[pick], prompt, greedy,
                                    f"ab-{i}")
            cached += c
            prompt_toks += len(prompt)
        return {"cached_token_ratio": round(cached / prompt_toks, 4),
                "cached_tokens": cached, "prompt_tokens": prompt_toks}

    routing_ab = {"aware": routing_run(True), "blind": routing_run(False)}

    import jax

    doc = {
        "metric": "llm_kvtier_cache",
        "device": str(jax.devices()[0]),
        "platform": jax.devices()[0].platform,
        "workload": {
            "shared_prefix_tokens": len(sys_prefix),
            "suffix_tokens": BS,
            "rounds": rounds,
            "hbm_blocks": 16,
            "fillers_per_round": 3,
        },
        "tiers": tiers,
        "token_identical": identical,
        "routing_ab": routing_ab,
        "gates": {
            "deepest_hit_rate_exceeds_hbm_only":
                tiers["host_object"]["hit_rate"] > tiers["hbm_only"]["hit_rate"],
            "ttft_p50_no_worse":
                tiers["host_object"]["ttft_p50_ms"]
                <= tiers["hbm_only"]["ttft_p50_ms"] * 1.10,
            "aware_beats_blind":
                routing_ab["aware"]["cached_token_ratio"]
                > routing_ab["blind"]["cached_token_ratio"],
        },
    }
    _write_capture(args.kvtier_out, doc)
    return doc


# ---------------------------------------------------------------------------
# --kvfetch: cross-engine resurrection + prefetch + async spill (r18)
# ---------------------------------------------------------------------------


def run_kvfetch_bench(args) -> dict:
    """Three experiments, one capture (the r18 rungs of the tiered
    cache):

    1+2. CROSS-ENGINE / PREFETCH A/B — two same-weights engines share a
       prefix index + fetch registry. Several system-prompt families
       are warmed on the OWNER engine and thrashed into its host tier;
       the owner then sits at queue depth past the routing slack (the
       hot-holder pile-up case). Each measured request runs through the
       REAL routing helper (best_prefix_replica):
         * r17 route-to-owner arm (fetch_weight=0): the owner is past
           slack, so the pick degrades to the depth ladder — the cold
           engine serves it with a FULL RECOMPUTE (the r17 failure
           mode this PR removes);
         * fetch-aware arm: the cold engine scores fetch_weight x the
           owner's holding, wins the pick, and its prefetch worker
           PULLS the prefix over the fetch plane while the request
           waits — admission finds the blocks resident.
       Gates: identical tokens, fetch-aware cached-token ratio >=
       route-to-owner's, and TTFT p50 with prefetch <= without.

    3. ASYNC SPILL WALL — one engine thrashed identically under
       async_spill on/off; we compare the per-eviction wall time spent
       INSIDE the allocation path (capture-only vs the r17 blocking
       device->host gather + CRC). Gate: async p99 < blocking p99.
    """
    import numpy as np

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.kvfetch import LocalFetchClient, LocalFetchRegistry
    from ray_tpu.llm.kvtier import (
        KVTierConfig,
        LocalPrefixIndex,
        chain_hashes,
    )
    from ray_tpu.llm.kvtier.index import best_prefix_replica
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import llama

    BS = 16
    # recomputing the shared prefix must cost real compute (the TTFT
    # comparison prices recompute vs fetch+scatter): the r17 bench model
    model = llama.LlamaConfig(
        vocab_size=512, d_model=192, n_layers=4, n_heads=6, n_kv_heads=2,
        d_ff=384, max_seq=512, remat=False,
    )
    import jax as _jax

    params = llama.init_params(model, _jax.random.key(0))
    rng = np.random.RandomState(args.kvfetch_seed)
    n_fam = max(4, args.kvfetch_rounds)
    families = [list(rng.randint(3, 200, size=20 * BS)) for _ in range(n_fam)]
    greedy = SamplingParams(max_tokens=8, temperature=0.0)
    kvt_cfg = KVTierConfig(host_bytes=64 << 20, object_bytes=0)

    def eng_cfg(kvt):
        return EngineConfig(model=model, num_blocks=40, block_size=BS,
                            max_num_seqs=4, max_prefill_len=512, kvtier=kvt)

    def run_once(eng, prompt, sp, rid, pre=None):
        """(ttft_s, cached, toks); ``pre`` runs after add_request and
        INSIDE the TTFT window (the prefetch wait is honestly priced)."""
        t0 = time.perf_counter()
        eng.add_request(prompt, sp, request_id=rid)
        if pre is not None:
            pre()
        ttft = cached = None
        toks = []
        while eng.has_unfinished():
            for o in eng.step():
                if o.request_id != rid:
                    continue
                if ttft is None and o.new_token_ids:
                    ttft = time.perf_counter() - t0
                    cached = o.num_cached_tokens
                if o.finished:
                    toks = o.output_token_ids
        return ttft, cached or 0, toks

    def suffix(i):
        return list(np.random.RandomState(900 + i).randint(3, 200, size=BS))

    warm_fam = list(np.random.RandomState(8888).randint(3, 200, size=20 * BS))

    def make_pair(tag, attach_fetch):
        idx = LocalPrefixIndex()
        reg = LocalFetchRegistry()
        owner = LLMEngine(eng_cfg(kvt_cfg), params=params, seed=0)
        cold = LLMEngine(eng_cfg(kvt_cfg), params=params, seed=0)
        owner.kvtier.attach_index(idx, engine_key="owner")
        cold.kvtier.attach_index(idx, engine_key="cold")
        reg.register("owner", owner.kvtier)
        reg.register("cold", cold.kvtier)
        if attach_fetch:
            # the r17 arm gets NO fetch plane: a cold replica there can
            # only recompute (exactly the behavior this PR replaces)
            cold.kvfetch.attach(LocalFetchClient(reg))
        # warm every family on the owner, then thrash its 40-block HBM
        # so the families live only in its host tier
        for f, fam in enumerate(families + [warm_fam]):
            run_once(owner, fam + suffix(f), greedy, f"warm-{tag}-{f}")
        for j in range(6):
            run_once(owner, list(np.random.RandomState(3000 + j).randint(
                3, 200, size=24 * BS)),
                SamplingParams(max_tokens=2, temperature=0.0),
                f"thrash-{tag}-{j}")
        owner.kvtier.flush_spills()
        owner.kvtier.flush_index(force=True)
        # jit warmup on the cold engine, excluded from measurements:
        # the plain prefill bucket, and (fetch arm) one full
        # fetch -> prefetch -> scatter cycle so the kv-import program
        # compiles outside the measured TTFT window
        run_once(cold, list(np.random.RandomState(77).randint(
            3, 200, size=21 * BS)), greedy, f"jit-{tag}")
        if attach_fetch:
            run_once(cold, warm_fam + suffix(997), greedy,
                     f"jit-fetch-{tag}",
                     pre=lambda: (cold.kvfetch.wait_idle(20),
                                  cold.kvfetch.tick()))
        return idx, owner, cold

    def routing_arm(fetch_aware: bool) -> dict:
        tag = "aware" if fetch_aware else "r17"
        idx, owner, cold = make_pair(tag, attach_fetch=fetch_aware)
        # the owner pool sits past the routing slack (hot holder)
        depths = {"owner": kvt_cfg.depth_slack + 2, "cold": 0}
        fw = kvt_cfg.fetch_weight if fetch_aware else 0.0
        engines = {"owner": owner, "cold": cold}
        cached = prompt_toks = 0
        picked: dict = {}
        ttfts = []
        token_ids = []
        for i, fam in enumerate(families):
            prompt = fam + suffix(1000 + i)
            lookup = idx.lookup(chain_hashes(prompt, BS))
            pick = best_prefix_replica(lookup, depths, cfg=kvt_cfg,
                                       fetch_weight=fw)
            if pick is None:
                pick = min(depths, key=lambda k: depths[k])  # the ladder
            picked[pick] = picked.get(pick, 0) + 1
            eng = engines[pick]
            pre = None
            if pick == "cold" and fetch_aware:
                # the prefetch pull runs while the request queues; its
                # wall is INSIDE the measured TTFT window
                pre = lambda: (cold.kvfetch.wait_idle(20),
                               cold.kvfetch.tick())
            ttft, c, toks = run_once(eng, prompt, greedy,
                                     f"m-{tag}-{i}", pre=pre)
            ttfts.append(ttft * 1e3)
            cached += c
            prompt_toks += len(prompt)
            token_ids.append(toks)
        st = cold.stats()
        return {
            "cached_token_ratio": round(cached / prompt_toks, 4),
            "cached_tokens": cached,
            "prompt_tokens": prompt_toks,
            "ttft_ms": _dist(ttfts),
            "ttft_p50_ms": _dist(ttfts)["p50"],
            "picks": picked,
            "cold_fetch": (st["kv_tiers"].get("fetch") or {}).get("remote"),
            "token_ids": token_ids,
        }

    aware = routing_arm(True)
    r17 = routing_arm(False)
    # correctness rail: a fetched/prefetched prefix must not change one
    # token vs the recompute arm
    identical = aware["token_ids"] == r17["token_ids"]
    for arm in (aware, r17):
        del arm["token_ids"]

    # -- async spill wall ------------------------------------------------------
    def spill_arm(async_spill: bool) -> dict:
        kvt = KVTierConfig(host_bytes=64 << 20, object_bytes=0,
                           async_spill=async_spill, prefetch=False)
        eng = LLMEngine(eng_cfg(kvt), params=params, seed=0)
        for f, fam in enumerate(families[:4]):
            run_once(eng, fam + suffix(f), greedy, f"w-{async_spill}-{f}")
        for j in range(args.kvfetch_rounds):
            run_once(eng, list(np.random.RandomState(5000 + j).randint(
                3, 200, size=24 * BS)),
                SamplingParams(max_tokens=2, temperature=0.0),
                f"t-{async_spill}-{j}")
        eng.kvtier.flush_spills()
        walls = sorted(eng.kvtier.spill_wall_ms)

        def pct(p):
            return walls[min(len(walls) - 1, int(len(walls) * p))]

        return {
            "evictions": len(walls),
            "wall_p50_ms": round(pct(0.5), 4),
            "wall_p99_ms": round(pct(0.99), 4),
            "wall_mean_ms": round(sum(walls) / max(1, len(walls)), 4),
            "host_entries": eng.kvtier.stats()["host"]["entries"],
        }

    spill = {"async": spill_arm(True), "blocking": spill_arm(False)}

    import jax

    doc = {
        "metric": "llm_kvfetch_cache",
        "device": str(jax.devices()[0]),
        "platform": jax.devices()[0].platform,
        "workload": {
            "families": n_fam,
            "family_prefix_tokens": 20 * BS,
            "suffix_tokens": BS,
            "owner_depth_past_slack": True,
            "hbm_blocks": 40,
        },
        "cross_engine": {"fetch_aware": aware, "route_to_owner": r17},
        "token_identical": identical,
        "spill_wall": spill,
        "gates": {
            "token_identical": identical,
            "aware_ratio_at_least_r17":
                aware["cached_token_ratio"] >= r17["cached_token_ratio"],
            "prefetch_ttft_p50_no_worse":
                aware["ttft_p50_ms"] <= r17["ttft_p50_ms"],
            "async_spill_wall_p99_lower":
                spill["async"]["wall_p99_ms"]
                < spill["blocking"]["wall_p99_ms"],
        },
    }
    _write_capture(args.kvfetch_out, doc)
    return doc


def main():
    import os

    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", action="store_true",
                    help="run the speculative-decoding benchmark "
                    "(spec vs baseline on repetitive prompts) instead")
    ap.add_argument("--spec-out", default=_SPEC_OUT)
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify pass")
    ap.add_argument("--trace", action="store_true",
                    help="also write the per-phase request-latency "
                    "breakdown from the ray_tpu.obs flight recorder")
    ap.add_argument("--trace-out", default=_TRACE_OUT)
    ap.add_argument("--disagg", action="store_true",
                    help="run the mixed-load disaggregated-vs-colocated "
                    "TPOT benchmark instead")
    ap.add_argument("--disagg-out", default=_DISAGG_OUT)
    ap.add_argument("--disagg-connector", default="inproc",
                    choices=["inproc", "rpc", "device"])
    ap.add_argument("--pipeline", action="store_true",
                    help="run the sync-vs-pipelined decode A/B "
                    "(ray_tpu.llm.pipeline) instead")
    ap.add_argument("--pipeline-out", default=_PIPELINE_OUT)
    ap.add_argument("--mixed", action="store_true",
                    help="split-vs-mixed ragged dispatch A/B "
                         "(EngineConfig.mixed_batch, ray_tpu.llm.mixed)")
    ap.add_argument("--mixed-out", default=_MIXED_OUT)
    ap.add_argument("--chaos", action="store_true",
                    help="run the availability-SLO benchmark under seeded "
                    "engine preemption instead")
    ap.add_argument("--chaos-out", default=_CHAOS_OUT)
    ap.add_argument("--chaos-seed", type=int, default=1234)
    ap.add_argument("--chaos-rate", type=float, default=0.08,
                    help="per-step preemption probability (bounded by the "
                    "spec's max_fires so the recovery budget holds)")
    ap.add_argument("--kvtier", action="store_true",
                    help="run the tiered-prefix-cache benchmark instead "
                    "(hit-rate + TTFT as tiers deepen, plus the "
                    "prefix-aware-routing A/B)")
    ap.add_argument("--kvtier-out", default=_KVTIER_OUT)
    ap.add_argument("--kvtier-seed", type=int, default=7)
    ap.add_argument("--kvtier-rounds", type=int, default=8)
    ap.add_argument("--kvfetch", action="store_true",
                    help="run the cross-engine resurrection / prefetch "
                    "/ async-spill benchmark instead (fetch-aware vs "
                    "r17 route-to-owner A/B)")
    ap.add_argument("--kvfetch-out", default=_KVFETCH_OUT)
    ap.add_argument("--kvfetch-seed", type=int, default=11)
    ap.add_argument("--kvfetch-rounds", type=int, default=8)
    args = ap.parse_args()

    from ray_tpu.utils.backend import open_backend

    # every mode needs the chip; a smoke shape on the CPU only runs when
    # JAX_PLATFORMS=cpu asked for it (tier-1 does)
    open_backend()

    if args.spec:
        print(json.dumps(run_spec_bench(args)))
        return
    if args.pipeline:
        print(json.dumps(run_pipeline_bench(args)))
        return
    if args.disagg:
        print(json.dumps(run_disagg_bench(args)))
        return
    if args.mixed:
        print(json.dumps(run_mixed_bench(args)))
        return
    if args.chaos:
        print(json.dumps(run_chaos_bench(args)))
        return
    if args.kvtier:
        print(json.dumps(run_kvtier_bench(args)))
        return
    if args.kvfetch:
        print(json.dumps(run_kvfetch_bench(args)))
        return

    from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.models import llama

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = llama.LLAMA_400M
        n_requests, prompt_len, max_new = 32, 128, 128
    else:
        cfg = llama.LLAMA_TINY
        n_requests, prompt_len, max_new = 8, 16, 16

    engine = LLMEngine(
        EngineConfig(
            model=cfg,
            max_num_seqs=min(n_requests, 16),
            num_blocks=1024 if on_tpu else 128,
        )
    )
    import numpy as np

    rng = np.random.default_rng(0)
    params = SamplingParams(max_tokens=max_new, temperature=0.0, ignore_eos=True)

    def run(n):
        t0 = time.perf_counter()
        for i in range(n):
            engine.add_request(
                rng.integers(1, cfg.vocab_size, prompt_len).tolist(),
                params,
                request_id=f"r{time.monotonic_ns()}-{i}",
            )
        generated = 0
        first = None
        while engine.has_unfinished():
            for o in engine.step():
                if o.new_token_ids:
                    if first is None:
                        first = time.perf_counter()
                    generated += len(o.new_token_ids)
        return generated, time.perf_counter() - t0, (first or t0) - t0

    # warmup pass compiles every (bucket, chunk, table-width) shape —
    # a cold compile costs seconds per shape and would otherwise be
    # billed to throughput; serving numbers are steady-state
    run(min(n_requests, 16))
    if args.trace:
        # the report should describe the steady-state timed pass only,
        # not the compile-heavy warmup traces
        from ray_tpu.obs import get_recorder

        get_recorder().clear()
    generated, dt, ttft = run(n_requests)

    expected = n_requests * max_new
    result = {
        "metric": "llm_decode_tok_s" if on_tpu else "llm_decode_smoke_tok_s",
        "value": round(generated / dt, 1),
        "unit": "tok/s",
        "vs_baseline": 0,
        "generated_tokens": generated,
        "expected_tokens": expected,
        "wall_s": round(dt, 2),
        "ttft_s": round(ttft, 3),
        "concurrency": min(n_requests, 16),
        "model_params": cfg.num_params(),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
    }
    if generated < expected * 0.9:
        result["warning"] = "fewer tokens than expected (early stops?)"

    if args.trace:
        from ray_tpu.obs import get_recorder

        report = {
            "metric": "llm_serving_trace" if on_tpu else "llm_serving_trace_smoke",
            "decode_chunk": engine.config.decode_chunk,
            "concurrency": min(n_requests, 16),
            "max_new": max_new,
            "device": getattr(jax.devices()[0], "device_kind", "cpu"),
            **build_trace_report(get_recorder()),
        }
        _write_capture(args.trace_out, report)
        result["trace_out"] = args.trace_out
        result["trace_coverage_pct_mean"] = report["coverage_pct_mean"]
        if report["phases_ms"]:
            result["trace_top_phase_ms"] = max(
                report["phases_ms"].items(),
                key=lambda kv: kv[1].get("total", 0.0),
            )[0]

    print(json.dumps(result))


if __name__ == "__main__":
    main()
