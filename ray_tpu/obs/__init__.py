"""ray_tpu.obs — end-to-end request tracing + flight recorder + SLO metrics.

Three pieces:

 * context — ``TraceContext`` (W3C-traceparent shaped), carried by
   contextvar within a process and serialized into TaskSpecs, cluster
   RPC envelopes, and serve dispatch so one trace_id follows a request
   across API -> router -> engine -> cluster workers;
 * recorder — ``SpanRecorder``, a bounded flight recorder of the last N
   requests' spans (``obs.span(...)`` records + propagates in one call);
 * layer spans — ``obs.layer_span(name)`` where a LAYER does its work
   (the runner's turn, the engine's step, a trainer's report): an
   annotation in the JAX profiler's trace, always-on count and busy
   seconds (``obs.layer_counters()``), and a ``Span`` in the recorder's
   layer ring while ``obs.capture()`` is open; ``obs.compile_log()`` is
   the process's compiles and cache loads (utils/compile_cache.py);
 * the step's host timeline — for ``train.step`` (the step's call, which
   ``note_program``'s wrapper times), ``train.report`` and ``host.gc``
   (the collector's pauses, ``obs.watch_gc()``) the recorder keeps every
   use, traced or not (``obs.layer_timeline(name)``);
   ``obs.step_timeline()`` is a row a step (dispatch, wait, report,
   between, the thread's and the other threads' CPU, pauses) and
   ``obs.slow_steps()`` the steps over 1.2 x the median with one cause
   each;
 * programs — ``obs.note_program(name, jitted)`` keeps what finds a
   compiled program again (train/step.py notes ``train.step``);
   ``obs.op_names()`` is its instructions with the named scopes each ran
   under;
 * slo — serving SLO histograms (TTFT / TPOT / queue-wait / e2e +
   router dispatch latency) on the util/metrics Prometheus registry;
 * telemetry — the CLUSTER-WIDE metrics plane (import
   ``ray_tpu.obs.telemetry`` directly): per-process registries ship
   monotonic snapshots to the GCS (heartbeat piggyback / telemetry_push),
   which serves counter sums, bucket-merged histogram percentiles,
   role/pool rollups, SLO grades, a merged Prometheus exposition, and
   the ``scripts/ray_tpu_status.py`` one-query status report.

Instrumented surfaces: ``GET /api/trace`` on the dashboard (request
spans merged with the task timeline), ``GET /v1/requests`` +
``GET /v1/requests/{rid}/trace`` on the OpenAI app, and
``llm_serving_bench.py --trace``.
"""

from ray_tpu.obs.context import (
    TraceContext,
    attach,
    current,
    detach,
    new_context,
    use,
)
from ray_tpu.obs.programs import note as note_program, op_names
from ray_tpu.obs.recorder import (
    Span,
    SpanRecorder,
    clock_offset,
    get_recorder,
    layer_record,
    layer_span,
    span,
    unwatch_gc,
    watch_gc,
)
from ray_tpu.obs.steps import slow_steps, step_timeline


def layer_counters() -> dict:
    """{name: {"count", "busy_s", "max_s"}} of this process's layer spans; no lock."""
    return get_recorder().layer_counters()


def layer_timeline(name: str, since: float = 0.0) -> list:
    """[(start, end, extra)] of the last 4,096 uses of a timeline name
    (``train.step``, ``train.report``, ``host.gc``) that started at or
    after ``since``, kept whether or not a capture was open."""
    return get_recorder().layer_timeline(name, since)


def capture():
    """``with obs.capture() as spans:`` — layer spans become ``Span``s
    while the block runs; ``spans`` holds them once it has ended."""
    return get_recorder().capture()


def compile_log(since: float = 0.0) -> list:
    """[(time.time(), program name, seconds, "compiled" | "loaded")] of
    this process from ``since`` on, oldest first
    (ray_tpu.utils.compile_cache)."""
    from ray_tpu.utils.compile_cache import compile_log as log

    return log(since)


__all__ = [
    "TraceContext",
    "attach",
    "current",
    "detach",
    "new_context",
    "use",
    "Span",
    "SpanRecorder",
    "capture",
    "clock_offset",
    "compile_log",
    "get_recorder",
    "layer_counters",
    "layer_record",
    "layer_span",
    "layer_timeline",
    "note_program",
    "op_names",
    "slow_steps",
    "span",
    "step_timeline",
    "unwatch_gc",
    "watch_gc",
]
