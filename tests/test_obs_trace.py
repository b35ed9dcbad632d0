"""ray_tpu.obs tests: trace context, flight recorder, propagation
through serve/engine/core planes, SLO metrics, bench --trace smoke.

Covers the r08 acceptance contract: a request issued through the OpenAI
app yields a retrievable trace whose spans cover >=90% of its measured
e2e wall-clock, and /metrics exposes non-empty TTFT/TPOT histograms
after the run.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import obs
from ray_tpu.obs import context as trace_context
from ray_tpu.obs.recorder import Span, SpanRecorder

pytestmark = pytest.mark.trace


@pytest.fixture(autouse=True)
def rt():
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=16)
    yield


# ---------------------------------------------------------------------------
# context
# ---------------------------------------------------------------------------


def test_trace_context_roundtrip():
    ctx = trace_context.new_context()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16

    header = ctx.to_traceparent()
    back = trace_context.TraceContext.from_traceparent(header)
    assert back == ctx

    assert trace_context.TraceContext.from_traceparent("garbage") is None
    assert trace_context.TraceContext.from_traceparent(None) is None

    d = ctx.to_dict()
    assert trace_context.TraceContext.from_dict(d) == ctx
    assert trace_context.TraceContext.from_dict(None) is None
    assert trace_context.TraceContext.from_dict({}) is None

    child = ctx.child()
    assert child.trace_id == ctx.trace_id and child.span_id != ctx.span_id


def test_contextvar_carry():
    assert trace_context.current() is None
    ctx = trace_context.new_context()
    with trace_context.use(ctx):
        assert trace_context.current() is ctx
        with obs.span("inner") as child:
            assert child.trace_id == ctx.trace_id
            assert trace_context.current() is child
        assert trace_context.current() is ctx
    assert trace_context.current() is None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def _mk_span(trace_id, name="s", start=0.0, end=1.0, parent=None):
    return Span(trace_id=trace_id, span_id=os.urandom(8).hex(),
                parent_id=parent, name=name, start=start, end=end)


def test_flight_recorder_drop_oldest_bounds_memory():
    rec = SpanRecorder(max_traces=4, max_spans_per_trace=8)
    for i in range(10):
        tid = f"{i:032x}"
        for j in range(3):
            rec.add(_mk_span(tid, name=f"s{j}", start=float(i), end=float(i) + 1))
    assert len(rec) == 4
    assert rec.num_dropped_traces == 6
    # oldest gone, newest kept
    assert rec.get(f"{0:032x}") == []
    assert len(rec.get(f"{9:032x}")) == 3
    # per-trace span cap drops the OLDEST spans: the llm.request/api.*
    # roots are recorded last (at finish) and must survive a long
    # generation's flood of decode-round spans
    tid = "f" * 32
    for j in range(20):
        rec.add(_mk_span(tid, name=f"s{j}"))
    kept = [s.name for s in rec.get(tid)]
    assert len(kept) == 8
    assert "s19" in kept and "s0" not in kept
    assert rec.num_dropped_spans == 12


def test_chrome_trace_export_is_bounded_with_truncated_flag():
    """Satellite r11: a large trace's Chrome-trace export must be capped
    (span-count limit + explicit truncated flag) so it can never blow
    past the cluster RPC MAX_FRAME guard or an openable HTTP response."""
    rec = SpanRecorder(max_traces=8, max_spans_per_trace=100)
    tid = "a" * 32
    for j in range(50):
        rec.add(_mk_span(tid, name=f"s{j}", start=float(j), end=float(j) + 1))
    bounded = rec.chrome_trace_bounded(max_events=10)
    assert bounded["truncated"] is True
    assert bounded["total_spans"] == 50
    assert len(bounded["events"]) == 10
    # deterministic: the EARLIEST events survive (ascending time sort)
    assert [e["ts"] for e in bounded["events"]] == sorted(
        e["ts"] for e in bounded["events"]
    )
    assert bounded["events"][0]["ts"] == 0.0
    # under the cap: untouched, flag off
    free = rec.chrome_trace_bounded(max_events=1000)
    assert free["truncated"] is False
    assert len(free["events"]) == 50
    # list-returning compat surface honors the cap too
    assert len(rec.chrome_trace(max_events=10)) == 10
    # per-trace filter composes with the cap
    only = rec.chrome_trace_bounded(trace_id=tid, max_events=5)
    assert only["truncated"] and len(only["events"]) == 5


def test_openai_request_trace_is_bounded():
    """GET /v1/requests/{rid}/trace caps its span list and says so."""
    rec = obs.get_recorder()
    tid = "b" * 32
    for j in range(30):
        rec.add(_mk_span(tid, name=f"s{j}", start=float(j), end=float(j) + 1))

    from ray_tpu.llm.openai_api import LLMServer

    class _FakeApp:
        TRACE_MAX_SPANS = 8
        request_trace = LLMServer.request_trace

    resp = _FakeApp().request_trace(tid)
    assert resp["truncated"] is True
    assert resp["total_spans"] == 30
    assert len(resp["spans"]) == 8
    # earliest-first, so the root/arrival side of the trace survives
    assert [s["start"] for s in resp["spans"]] == sorted(
        s["start"] for s in resp["spans"]
    )


def test_recorder_request_index_and_summary():
    rec = SpanRecorder(max_traces=4)
    ctx = trace_context.new_context()
    rec.record("phase.a", 0.0, 4.0, ctx=ctx)
    rec.record("phase.b", 4.0, 9.0, ctx=ctx)
    rec.record("root", 0.0, 10.0, ctx=ctx, attrs={"request_id": "req-42"})
    assert rec.find_by_request("req-42") == ctx.trace_id
    s = rec.summary(ctx.trace_id)
    assert s["root"] == "root" and s["e2e_s"] == 10.0
    assert s["coverage_pct"] == 90.0  # 9s of 10 covered
    # request_id eviction follows trace eviction
    for i in range(4):
        rec.add(_mk_span(f"{i:032x}"))
    assert rec.find_by_request("req-42") is None


# ---------------------------------------------------------------------------
# core plane: task events carry trace ids
# ---------------------------------------------------------------------------


def test_task_events_carry_trace_id():
    @ray_tpu.remote
    def traced(x):
        return x + 1

    with obs.span("test.root") as ctx:
        ref = traced.remote(1)
        assert ray_tpu.get(ref) == 2

    from ray_tpu.util import state

    rows = [t for t in state.list_tasks() if "traced" in t.name]
    assert rows, "task not recorded"
    assert any(t.trace_id == ctx.trace_id for t in rows)

    trace = state.timeline()
    spans = [e for e in trace if "traced" in e["name"]]
    assert any(
        e.get("args", {}).get("trace_id") == ctx.trace_id for e in spans
    ), "timeline span lost the trace id"


def test_actor_task_carries_trace_and_nested_span():
    @ray_tpu.remote
    class Echo:
        def trace_id(self):
            cur = trace_context.current()
            return cur.trace_id if cur else None

    a = Echo.remote()
    with obs.span("test.actor_root") as ctx:
        got = ray_tpu.get(a.trace_id.remote())
    assert got == ctx.trace_id


# ---------------------------------------------------------------------------
# serve plane: handle dispatch propagates the caller's trace
# ---------------------------------------------------------------------------


def test_serve_replica_span_carries_caller_trace():
    from ray_tpu import serve

    @serve.deployment
    class Traced:
        def __call__(self):
            cur = trace_context.current()
            return cur.trace_id if cur else None

    try:
        handle = serve.run(Traced.bind(), name="traced_app", route_prefix=None)
        with obs.span("test.serve_root") as ctx:
            got = handle.remote().result()
        assert got == ctx.trace_id, "replica executed outside the caller's trace"
        # the replica + serve.request spans landed in the flight recorder
        deadline = time.time() + 5
        names = set()
        while time.time() < deadline:
            names = {s.name for s in obs.get_recorder().get(ctx.trace_id)}
            if "serve.replica" in names and "serve.request" in names:
                break
            time.sleep(0.05)
        assert "serve.replica" in names and "serve.request" in names, names
        # the replica span NESTS under the serve.request span: its parent
        # must be a span that actually exists in the trace
        spans = obs.get_recorder().get(ctx.trace_id)
        replica = next(s for s in spans if s.name == "serve.replica")
        request = next(s for s in spans if s.name == "serve.request")
        assert replica.parent_id == request.span_id
        # router dispatch latency histogram populated
        from ray_tpu.util import metrics as metrics_mod

        text = metrics_mod.prometheus_text()
        assert "ray_tpu_serve_router_dispatch_seconds_count" in text
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------------
# engine lifecycle: SLO histograms + span phases
# ---------------------------------------------------------------------------


def _tiny_engine(**over):
    import jax.numpy as jnp

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models import llama

    cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    kw = dict(model=cfg, num_blocks=64, block_size=8, max_num_seqs=4,
              max_prefill_len=32)
    kw.update(over)
    return LLMEngine(EngineConfig(**kw))


def test_engine_generate_populates_slo_histograms_and_phases():
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.util import metrics as metrics_mod

    eng = _tiny_engine()
    eng.model_tag = "tiny-slo-phases"
    sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    rid = eng.add_request([1, 2, 3, 4], sp)
    req = eng.requests[rid]
    while eng.has_unfinished():
        eng.step()

    # phase spans tile arrival -> finish
    spans = obs.get_recorder().get(req.trace.trace_id)
    names = {s.name for s in spans}
    assert {"engine.queue_wait", "engine.prefill", "llm.request"} <= names, names
    assert "engine.decode_chunk" in names or "engine.spec_round" in names
    s = obs.get_recorder().summary(req.trace.trace_id)
    assert s["coverage_pct"] >= 90.0, s
    assert s["attrs"]["request_id"] == rid
    assert s["attrs"]["ttft_s"] > 0 and s["attrs"]["e2e_s"] >= s["attrs"]["ttft_s"]

    text = metrics_mod.prometheus_text()
    assert 'ray_tpu_llm_ttft_seconds_count{model="tiny-slo-phases"} 1' in text
    assert 'ray_tpu_llm_tpot_seconds_count{model="tiny-slo-phases"} 1' in text
    assert 'ray_tpu_llm_queue_wait_seconds_count{model="tiny-slo-phases"} 1' in text
    assert 'model="tiny-slo-phases",finish_reason="length"' in text  # e2e series


def test_engine_abort_records_root_span():
    from ray_tpu.llm.sampling import SamplingParams

    eng = _tiny_engine()
    rid = eng.add_request([1, 2, 3], SamplingParams(max_tokens=64))
    req = eng.requests[rid]
    eng.step()  # prefill + first token
    eng.abort_request(rid)
    spans = obs.get_recorder().get(req.trace.trace_id)
    roots = [s for s in spans if s.name == "llm.request"]
    assert roots and roots[0].attrs["finish_reason"] == "abort"


# ---------------------------------------------------------------------------
# OpenAI app end-to-end: the r08 acceptance contract
# ---------------------------------------------------------------------------


def test_openai_app_trace_coverage_and_flight_recorder():
    import jax.numpy as jnp

    from ray_tpu import serve
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.llm.openai_api import LLMConfig, build_openai_app
    from ray_tpu.models import llama
    from ray_tpu.util import metrics as metrics_mod

    cfg = dataclasses.replace(llama.LLAMA_TINY, dtype=jnp.float32)
    llm_config = LLMConfig(
        model_id="tiny-traced",
        engine=EngineConfig(model=cfg, num_blocks=64, block_size=8,
                            max_num_seqs=4, max_prefill_len=32),
    )
    try:
        handle = build_openai_app(llm_config, name="traced_llm",
                                  route_prefix=None)
        body = {"prompt": "hello trace", "max_tokens": 12,
                "temperature": 0.0}
        out = handle.options(method_name="completions").remote(body).result(
            timeout_s=180
        )
        assert out["choices"][0]["text"] is not None
        rid = out["id"]
        assert out["trace_id"], "completion payload lost its trace_id"

        # retrievable trace via the flight-recorder surface
        doc = handle.options(method_name="request_trace").remote(rid).result(
            timeout_s=60
        )
        assert doc["trace_id"] == out["trace_id"]
        names = [s["name"] for s in doc["spans"]]
        assert "api.completions" in names
        assert "engine.queue_wait" in names and "engine.prefill" in names
        assert any(n in ("engine.decode_chunk", "engine.spec_round")
                   for n in names)
        # ACCEPTANCE: spans cover >=90% of the measured e2e wall-clock
        assert doc["coverage_pct"] >= 90.0, doc
        assert doc["e2e_s"] > 0

        # flight-recorder listing knows this request
        listing = handle.options(method_name="list_requests").remote().result(
            timeout_s=60
        )
        assert any(rid in m.get("request_ids", ())
                   for m in listing["data"]), listing

        # unknown request -> 404-shaped error, not a crash
        missing = handle.options(method_name="request_trace").remote(
            "cmpl-doesnotexist"
        ).result(timeout_s=60)
        assert missing["error"]["code"] == 404

        # ACCEPTANCE: /metrics exposes non-empty TTFT/TPOT histograms
        text = metrics_mod.prometheus_text()
        assert 'ray_tpu_llm_ttft_seconds_count{model="tiny-traced"}' in text
        assert 'ray_tpu_llm_tpot_seconds_count{model="tiny-traced"}' in text
        ttft_counts = [
            int(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith('ray_tpu_llm_ttft_seconds_count{model="tiny-traced"}')
        ]
        assert sum(ttft_counts) >= 1
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------------
# CI gate: metrics lint + bench --trace smoke
# ---------------------------------------------------------------------------


def _load_check_metrics():
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "scripts", "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_metrics_registry_clean():
    mod = _load_check_metrics()
    problems = mod.run_check()
    assert problems == [], problems


def test_check_metrics_catches_violations():
    from ray_tpu.util.metrics import Gauge, Histogram

    mod = _load_check_metrics()
    Gauge("ray_tpu_bad_metric_no_desc", description="")
    Histogram("ray_tpu_colliding", description="hist", boundaries=[1.0])
    Gauge("ray_tpu_colliding_count", description="collides with the hist")
    try:
        problems = mod.check_registry()
        assert any("missing description" in p for p in problems)
        assert any("_count series" in p for p in problems)
    finally:
        from ray_tpu.util import metrics as metrics_mod

        with metrics_mod._REGISTRY_LOCK:
            for name in ("ray_tpu_bad_metric_no_desc", "ray_tpu_colliding",
                         "ray_tpu_colliding_count"):
                metrics_mod._REGISTRY.pop(name, None)


def test_bench_trace_smoke_cpu():
    """llm_serving_bench.py --trace must run end to end under
    JAX_PLATFORMS=cpu (same bit-rot gate as the r07 --spec smoke)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_path = os.path.join("/tmp", f"trace_smoke_{os.getpid()}.json")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": repo})
    try:
        p = subprocess.run(
            [sys.executable,
             os.path.join(repo, "benchmarks", "llm_serving_bench.py"),
             "--trace", "--trace-out", out_path],
            env=env, capture_output=True, text=True, timeout=420,
        )
        assert p.returncode == 0, (p.stdout[-800:], p.stderr[-800:])
        line = [l for l in p.stdout.splitlines() if l.strip().startswith("{")][-1]
        result = json.loads(line)
        assert result["trace_coverage_pct_mean"] >= 90.0
        doc = json.loads(open(out_path).read())
        assert doc["metric"] == "llm_serving_trace_smoke"
        assert doc["requests"] > 0
        assert "engine.decode_chunk" in doc["phases_ms"]
        assert "engine.prefill" in doc["phases_ms"]
        assert doc["slo_s"]["ttft_s"]["n"] == doc["requests"]
    finally:
        if os.path.exists(out_path):
            os.remove(out_path)


def test_checked_in_trace_capture_keeps_coverage():
    """The checked-in TRACE_serving_r08.json keeps its honesty contract
    (refresh on the TPU when engine phases change)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "benchmarks", "TRACE_serving_r08.json")
    assert os.path.exists(path), "missing benchmarks/TRACE_serving_r08.json"
    doc = json.loads(open(path).read())
    assert doc["coverage_pct_mean"] >= 90.0
    assert doc["requests"] > 0
    assert doc["slo_s"]["e2e_s"]["n"] == doc["requests"]
