"""Observability export for StepProfiles.

Three sinks, all already wired to user-visible surfaces:

  * core/events.py TaskEventBuffer — segment spans become Chrome-trace
    "X" events (kind="profile"), so the legacy dashboard /timeline
    route and util.state.timeline() show the step breakdown next to
    task spans;
  * obs/recorder.py SpanRecorder — the same strip lands in the flight
    recorder as one bounded trace (root ``profile:{step}`` + one child
    span per segment), which is the AUTHORITATIVE profile stream for
    the unified /api/trace export: the recorder's drop-oldest caps
    (max_traces / max_spans_per_trace) bound it, and /api/trace filters
    the duplicate task-buffer copy out of its timeline half;
  * util/metrics.py Histograms/Gauges — per-segment wall time and
    step-level coverage/attainment land on the dashboard /metrics
    Prometheus endpoint for free.
"""

from __future__ import annotations

import itertools
import time
from typing import Optional

from ray_tpu.profiler.roofline import StepProfile
from ray_tpu.util.metrics import Gauge, Histogram

_span_counter = itertools.count()

# Boundaries tuned for step segments: micro-segments on CPU smoke models
# sit well under 1 ms; a whole-step segment of a large model reaches
# hundreds of ms.
_SEGMENT_MS_BOUNDARIES = [
    0.01, 0.05, 0.1, 0.5, 1, 2, 5, 10, 25, 50, 100, 250, 500, 1000,
]


def segment_histogram() -> Histogram:
    """The per-segment wall-time histogram (same storage every call:
    util.metrics shares series for same-name re-registrations)."""
    return Histogram(
        "profiler_segment_ms",
        description="profiler: attributed wall time per step segment (ms)",
        boundaries=_SEGMENT_MS_BOUNDARIES,
        tag_keys=("step", "segment", "bound"),
    )


def coverage_gauge() -> Gauge:
    from ray_tpu.obs.telemetry import AGG_MAX, declare_aggregation

    # cluster rollup: worst-profiled step wins (a fleet "coverage" sum
    # would be meaningless)
    declare_aggregation("profiler_step_coverage_pct", AGG_MAX)
    return Gauge(
        "profiler_step_coverage_pct",
        description="profiler: % of measured step time attributed to segments",
        tag_keys=("step",),
    )


def step_ms_gauge() -> Gauge:
    from ray_tpu.obs.telemetry import AGG_MAX, declare_aggregation

    declare_aggregation("profiler_step_ms", AGG_MAX)
    return Gauge(
        "profiler_step_ms",
        description="profiler: measured whole-step wall time (ms)",
        tag_keys=("step",),
    )


def export_metrics(profile: StepProfile) -> None:
    """Observe every segment + step-level gauges into the process-wide
    metrics registry (rendered by the dashboard /metrics route)."""
    hist = segment_histogram()
    for seg in profile.segments:
        hist.observe(
            seg.ms,
            tags={"step": profile.step, "segment": seg.name,
                  "bound": seg.bound},
        )
    coverage_gauge().set(profile.coverage_pct, tags={"step": profile.step})
    step_ms_gauge().set(profile.measured_step_ms, tags={"step": profile.step})


def emit_spans(profile: StepProfile, buffer=None, *,
               t_end: Optional[float] = None) -> int:
    """Reconstruct segment spans into the task event buffer.

    Segments are laid out back-to-back ending at ``t_end`` (default now),
    scaled to their attributed durations, so `ray timeline` / the
    dashboard /timeline route renders one profiled step as a contiguous
    strip. Returns the number of spans emitted."""
    if buffer is None:
        from ray_tpu.core import runtime as rt

        buffer = rt.get_runtime().task_events
    from ray_tpu.core.events import TaskState

    end = time.time() if t_end is None else t_end
    in_step = [s for s in profile.segments if s.in_step]
    total_s = sum(s.ms for s in in_step) / 1e3
    start = end - total_s
    n = 0
    cursor = start
    for seg in profile.segments:
        dur = seg.ms / 1e3
        if seg.in_step:
            t0, t1 = cursor, cursor + dur
            cursor = t1
        else:  # standalone segments stack before the step strip
            t0, t1 = start - dur, start
        span_id = f"profile-{profile.step}-{seg.name}-{next(_span_counter)}"
        name = f"profile:{profile.step}:{seg.name}"
        buffer.record(
            span_id, name, TaskState.RUNNING, kind="profile",
            worker=f"profiler:{profile.step}", ts=t0,
        )
        buffer.record(
            span_id, name, TaskState.FINISHED, kind="profile",
            worker=f"profiler:{profile.step}", ts=t1,
        )
        n += 1
    return n


def emit_recorder_spans(profile: StepProfile, recorder=None, *,
                        t_end: Optional[float] = None) -> str:
    """Mirror the profiled step into the obs flight recorder as ONE
    bounded trace: a root span ``profile:{step}`` covering the whole
    strip plus a child span per segment (same back-to-back layout as
    :func:`emit_spans`, standalone segments stacked before the strip).
    The recorder's drop-oldest caps make this the bounded profile
    stream /api/trace serves. Returns the trace id."""
    if recorder is None:
        from ray_tpu.obs.recorder import get_recorder

        recorder = get_recorder()
    from ray_tpu.obs.recorder import Span

    end = time.time() if t_end is None else t_end
    total_s = sum(s.ms for s in profile.segments if s.in_step) / 1e3
    standalone_s = max(
        (s.ms / 1e3 for s in profile.segments if not s.in_step), default=0.0
    )
    start = end - total_s
    trace_id = f"profile-{profile.step}-{next(_span_counter)}"
    root_id = f"{trace_id}-root"
    recorder.add(Span(
        trace_id=trace_id, span_id=root_id, parent_id=None,
        name=f"profile:{profile.step}",
        start=start - standalone_s, end=end,
        attrs={
            "step": profile.step,
            "measured_step_ms": profile.measured_step_ms,
            "coverage_pct": profile.coverage_pct,
        },
    ))
    cursor = start
    for seg in profile.segments:
        dur = seg.ms / 1e3
        if seg.in_step:
            t0, t1 = cursor, cursor + dur
            cursor = t1
        else:
            t0, t1 = start - dur, start
        recorder.add(Span(
            trace_id=trace_id,
            span_id=f"{trace_id}-{seg.name}",
            parent_id=root_id,
            name=f"profile:{profile.step}:{seg.name}",
            start=t0, end=t1,
            attrs={"ms": seg.ms, "bound": seg.bound,
                   "in_step": seg.in_step},
        ))
    return trace_id


def export(profile: StepProfile, buffer=None) -> None:
    """All sinks in one call — what the train/serve hooks use."""
    export_metrics(profile)
    t_end = time.time()
    emit_spans(profile, buffer, t_end=t_end)
    emit_recorder_spans(profile, t_end=t_end)
