"""Flight recorder: bounded in-process span store, last-N traces.

The serving analog of core/events.TaskEventBuffer: every instrumented
layer (OpenAI app, engine lifecycle, serve dispatch, replicas) records
``Span``s here keyed by trace_id. Capacity is bounded two ways —
``max_traces`` whole requests (drop-oldest, so a long-running server
always holds the most recent window) and ``max_spans_per_trace``
(a runaway generation cannot grow one trace without bound); drops are
counted, never silent.

Reads: ``get(trace_id)`` raw spans, ``traces()`` the flight-recorder
listing, ``summary(trace_id)`` e2e + span coverage honesty metrics,
``chrome_trace()`` Perfetto-ready events merged with the task
timeline by the dashboard ``/api/trace`` route.

Layer spans (``layer_span``) are the second kind of record: not "what
happened to request X" but "what was this layer doing" — the runner's
turn, the engine's step, a trainer's report. Each use is an annotation
in the JAX profiler's trace (so it sits beside the device's ops, on the
profiler's clock) plus two always-on numbers per name (count, busy
seconds). Only while a ``capture()`` is open do they also become
``Span``s, in a bounded ring of their own — never in the per-request
store, so they cannot evict a request.

The timeline is the third: for the few names that ask for it
(``TIMELINE_NAMES``: a train step's call, its report, the collector's
pauses) the last ``TIMELINE_MAX`` uses are kept as ``(start, end,
extra)`` whether a capture is open or not, so that "why was that step
slow" has an answer in a run nobody traced (``obs/steps.py`` reads it).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Optional

from ray_tpu.obs import context as trace_context

try:
    import resource
except ImportError:  # no getrusage on this platform: the switches read None
    resource = None
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)

TIMELINE_MAX = 4096  # events kept a timeline name; the oldest go first, counted
GC_NAME = "host.gc"
GC_KEEP_S = 1e-3     # a collection of generation 0 is kept in the ring only if longer
# the names whose every use is kept (registered here, in code: no configuration
# asks for a timeline); those in CLOCKED_NAMES also take the host's clocks at entry
TIMELINE_NAMES = ("train.step", "train.report", GC_NAME)
CLOCKED_NAMES = ("train.step",)


@dataclasses.dataclass
class Span:
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    start: float               # time.time() seconds
    end: float
    attrs: dict = dataclasses.field(default_factory=dict)
    status: str = "ok"         # ok | error

    @property
    def duration_s(self) -> float:
        return max(0.0, self.end - self.start)

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "duration_s": round(self.duration_s, 6),
            "attrs": dict(self.attrs),
            "status": self.status,
        }


class SpanRecorder:
    """Thread-safe ring of the last ``max_traces`` traces."""

    def __init__(self, max_traces: int = 256, max_spans_per_trace: int = 512,
                 max_layer_spans: int = 65536):
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, list[Span]]" = OrderedDict()
        self._meta: dict[str, dict] = {}
        self._by_request: dict[str, str] = {}  # request_id -> trace_id
        self.num_dropped_traces = 0
        self.num_dropped_spans = 0
        # layer spans: name -> [count, busy_s, max_s], added to under
        # _layer_lock (two additions and a compare) and READ WITHOUT IT;
        # the ring fills only while a capture is open
        self._layer_lock = threading.Lock()
        self._layer_counts: dict[str, list] = {}
        self._layers: "deque[Span]" = deque(maxlen=max_layer_spans)
        self._captures = 0
        self._layer_local = threading.local()
        self.num_dropped_layer_spans = 0
        # the timeline: name -> ring of (start, end, extra), always on
        # for the names that were asked for (keep_timeline)
        self._timelines: "dict[str, deque]" = {}
        self._clocked: set = set()
        self.num_dropped_timeline_events: dict[str, int] = {}

    # -- writes ---------------------------------------------------------------

    def _evict_oldest(self) -> None:
        old_tid, _ = self._traces.popitem(last=False)
        meta = self._meta.pop(old_tid, None)
        for rid in (meta or {}).get("request_ids", ()):
            self._by_request.pop(rid, None)
        self.num_dropped_traces += 1

    def add(self, span: Span) -> None:
        with self._lock:
            spans = self._traces.get(span.trace_id)
            if spans is None:
                while len(self._traces) >= self.max_traces:
                    self._evict_oldest()
                spans = self._traces[span.trace_id] = []
                self._meta[span.trace_id] = {
                    "trace_id": span.trace_id,
                    "root": span.name,
                    "_root_dur": span.duration_s,
                    "start": span.start,
                    "end": span.end,
                    "num_spans": 0,
                    "request_ids": [],
                }
            meta = self._meta[span.trace_id]
            if len(spans) >= self.max_spans_per_trace:
                # drop-oldest WITHIN the trace too: the request-level root
                # spans (llm.request / api.*) are recorded LAST, at finish
                # — dropping the newest would lose exactly the spans the
                # /v1/requests surface and SLO attrs are keyed on
                del spans[0]
                self.num_dropped_spans += 1
            spans.append(span)
            meta["num_spans"] = len(spans)
            meta["start"] = min(meta["start"], span.start)
            meta["end"] = max(meta["end"], span.end)
            # the listing labels a trace by its widest span (matches
            # summary()'s root selection): llm.request / api.completions
            # rather than whichever phase span happened to land first
            if span.parent_id is None or span.duration_s >= meta["_root_dur"]:
                meta["root"] = span.name
                meta["_root_dur"] = span.duration_s
            rid = span.attrs.get("request_id")
            if rid is not None and rid not in meta["request_ids"]:
                meta["request_ids"].append(rid)
                self._by_request[str(rid)] = span.trace_id

    def record(
        self,
        name: str,
        start: float,
        end: float,
        ctx: Optional[trace_context.TraceContext] = None,
        *,
        attrs: Optional[dict] = None,
        status: str = "ok",
    ) -> Optional[Span]:
        """Record one completed span under ``ctx`` (the span becomes a
        CHILD of ctx.span_id). Without a ctx the span starts its own
        trace. The explicit-ctx API exists for threads that don't carry
        the contextvar (the engine loop records against each Request's
        stored context)."""
        if ctx is None:
            ctx = trace_context.current() or trace_context.new_context()
        span = Span(
            trace_id=ctx.trace_id,
            span_id=trace_context._rand_hex(8),
            parent_id=ctx.span_id,
            name=name,
            start=start,
            end=end,
            attrs=dict(attrs or {}),
            status=status,
        )
        self.add(span)
        return span

    def resize(self, max_traces: Optional[int] = None,
               max_layer_spans: Optional[int] = None) -> None:
        """Set how many traces (and layer spans) are kept: a client that
        reads one window's requests at its end sizes the recorder to the
        window first. Shrinking drops the oldest, counted."""
        if max_traces is not None:
            with self._lock:
                self.max_traces = max(1, int(max_traces))
                while len(self._traces) > self.max_traces:
                    self._evict_oldest()
        if max_layer_spans is not None:
            with self._layer_lock:
                kept = deque(self._layers, maxlen=max(1, int(max_layer_spans)))
                self.num_dropped_layer_spans += len(self._layers) - len(kept)
                self._layers = kept

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()
            self._meta.clear()
            self._by_request.clear()
            self.num_dropped_traces = 0
            self.num_dropped_spans = 0
        with self._layer_lock:
            self._layers.clear()
            self._layer_counts.clear()
            self.num_dropped_layer_spans = 0
            for name, ring in self._timelines.items():
                ring.clear()
                self.num_dropped_timeline_events[name] = 0

    # -- layer spans ----------------------------------------------------------

    def layer_counters(self) -> dict:
        """{name: {"count", "busy_s", "max_s"}} of every layer span used
        in this process, capture or not (``max_s``: the longest single
        use, the tail a sum cannot show). Takes no lock: a reader may
        see the count of a span whose seconds land an instant later."""
        return {name: {"count": c[0], "busy_s": c[1], "max_s": c[2]}
                for name, c in list(self._layer_counts.items())}

    def keep_timeline(self, name: str, clocks: bool = False,
                      size: int = TIMELINE_MAX) -> None:
        """From now on keep every use of the layer span ``name`` as
        ``(start, end, extra)`` in a ring of the last ``size``, capture
        or not; with ``clocks`` its ``extra`` is ``host_clocks()`` at
        the span's entry."""
        with self._layer_lock:
            if name not in self._timelines:
                self._timelines[name] = deque(maxlen=max(1, int(size)))
                self.num_dropped_timeline_events[name] = 0
            if clocks:
                self._clocked.add(name)

    def layer_timeline(self, name: str, since: float = 0.0) -> list:
        """[(start, end, extra)] of the kept uses of ``name`` that
        started at or after ``since`` (time.time()), oldest first;
        ``extra`` is None, the clocks at entry (a clocked name) or what
        the recording site gave. [] for a name that keeps no timeline."""
        while True:
            try:
                with self._layer_lock:
                    kept = list(self._timelines.get(name, ()))
                break
            except RuntimeError:  # the collector's hook appended meanwhile: it takes no lock
                continue
        return [e for e in kept if e[0] >= since]

    def layer_spans(self, since: float = 0.0) -> list[Span]:
        """Captured layer spans that ended at or after ``since``
        (time.time()), in the order they ended."""
        with self._layer_lock:
            return [s for s in self._layers if s.end >= since]

    @contextlib.contextmanager
    def capture(self):
        """While open, layer spans are recorded as ``Span``s as well as
        counted. Yields a list that holds this capture's layer spans
        once the block has ended. A clock marker is written at both
        edges, so a profiler trace taken around the block can be placed
        on the recorder's clock (``clock_offset``)."""
        spans: list = []
        t0 = time.time()
        with self._layer_lock:
            self._captures += 1
        _clock_marker()
        try:
            yield spans
        finally:
            _clock_marker()
            with self._layer_lock:
                self._captures -= 1
            spans.extend(self.layer_spans(since=t0))

    def _count(self, name: str, start: float, end: float) -> None:
        cell = self._layer_counts.get(name)
        if cell is None:
            cell = self._layer_counts[name] = [0, 0.0, 0.0]
        seconds = max(0.0, end - start)
        cell[0] += 1
        cell[1] += seconds
        if seconds > cell[2]:
            cell[2] = seconds

    def _keep(self, name: str, event: tuple) -> None:
        ring = self._timelines[name]
        if len(ring) == ring.maxlen:
            self.num_dropped_timeline_events[name] += 1
        ring.append(event)

    def _layer_done(self, name: str, start: float, end: float,
                    ids: Optional[tuple], attrs: Optional[dict],
                    status: str = "ok", extra=None) -> None:
        with self._layer_lock:
            first = name not in self._layer_counts
            self._count(name, start, end)
            if name in self._timelines:
                self._keep(name, (start, end, extra))
            if ids is not None:
                if len(self._layers) == self._layers.maxlen:
                    self.num_dropped_layer_spans += 1
                trace_id, span_id, parent_id = ids
                self._layers.append(Span(trace_id, span_id, parent_id, name,
                                         start, end, dict(attrs or {}), status))
        if first and self is _RECORDER:
            # a new name in the process-wide recorder: make sure /metrics
            # carries the counters (outside the lock: this imports)
            from ray_tpu.util.metrics import register_collector

            register_collector(_export_layer_counters)

    # -- reads ----------------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)

    def get(self, trace_id: str) -> list[Span]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def since(self, t: float) -> dict:
        """{trace_id: [Span]} of every trace with a span that ended at
        or after ``t`` (time.time()), oldest trace first: one window's
        requests, taken at its end."""
        with self._lock:
            return {tid: list(spans) for tid, spans in self._traces.items()
                    if self._meta[tid]["end"] >= t}

    def find_by_request(self, request_id: str) -> Optional[str]:
        with self._lock:
            return self._by_request.get(str(request_id))

    def traces(self, limit: int = 100) -> list[dict]:
        """Flight-recorder listing, newest first."""
        with self._lock:
            metas = [
                {k: v for k, v in m.items() if not k.startswith("_")}
                for m in self._meta.values()
            ]
        metas.sort(key=lambda m: m["start"], reverse=True)
        for m in metas[:limit]:
            m["duration_s"] = round(max(0.0, m["end"] - m["start"]), 6)
        return metas[:limit]

    def summary(self, trace_id: str) -> Optional[dict]:
        """Root span + coverage honesty: % of the root's wall-clock
        covered by the union of its descendant spans (the profiler's
        coverage_pct idea applied to one request)."""
        spans = self.get(trace_id)
        if not spans:
            return None
        ids = {s.span_id for s in spans}
        roots = [s for s in spans if s.parent_id is None or s.parent_id not in ids]
        # widest orphan wins: engine-only traces have no API root span, so
        # every lifecycle span is parentless — the request-covering
        # llm.request span is the one coverage should be measured against
        root = max(roots or spans, key=lambda s: s.duration_s)
        children = [s for s in spans if s is not root]
        coverage = 0.0
        if root.duration_s > 0 and children:
            intervals = sorted(
                (max(s.start, root.start), min(s.end, root.end))
                for s in children
            )
            covered, cur_a, cur_b = 0.0, None, None
            for a, b in intervals:
                if b <= a:
                    continue
                if cur_b is None or a > cur_b:
                    if cur_b is not None:
                        covered += cur_b - cur_a
                    cur_a, cur_b = a, b
                else:
                    cur_b = max(cur_b, b)
            if cur_b is not None:
                covered += cur_b - cur_a
            coverage = 100.0 * covered / root.duration_s
        return {
            "trace_id": trace_id,
            "root": root.name,
            "start": root.start,
            "e2e_s": round(root.duration_s, 6),
            "num_spans": len(spans),
            "coverage_pct": round(coverage, 2),
            "attrs": dict(root.attrs),
        }

    # default export cap: ~200 bytes/event keeps the largest export well
    # under RPC framing / HTTP response sanity (a full recorder at
    # 256 traces x 512 spans is 131k spans ≈ tens of MB otherwise)
    DEFAULT_EXPORT_MAX_EVENTS = 50_000

    def chrome_trace(self, trace_id: Optional[str] = None,
                     max_events: Optional[int] = None) -> list[dict]:
        """Chrome trace-event JSON ("X" complete events); rows grouped
        by trace so one request reads as one strip in Perfetto.
        ``max_events`` caps the export (earliest-first after a time sort);
        use :meth:`chrome_trace_bounded` to also learn whether the cap
        bit."""
        return self.chrome_trace_bounded(
            trace_id=trace_id, max_events=max_events
        )["events"]

    def chrome_trace_bounded(self, trace_id: Optional[str] = None,
                             max_events: Optional[int] = None) -> dict:
        """Bounded export: {"events", "truncated", "total_spans"}. A large
        trace must not produce an export that blows past the cluster RPC
        MAX_FRAME guard (or an HTTP response nobody can open) — the cap
        drops the NEWEST events after an ascending time sort and says so
        instead of silently shipping everything."""
        cap = (self.DEFAULT_EXPORT_MAX_EVENTS
               if max_events is None else int(max_events))
        with self._lock:
            if trace_id is not None:
                groups = {trace_id: list(self._traces.get(trace_id, ()))}
            else:
                groups = {tid: list(sp) for tid, sp in self._traces.items()}
        out = []
        for tid, spans in groups.items():
            for s in spans:
                out.append({
                    "name": s.name,
                    "cat": "request" if s.status == "ok" else "request_error",
                    "ph": "X",
                    "ts": s.start * 1e6,
                    "dur": s.duration_s * 1e6,
                    "pid": f"trace:{tid[:8]}",
                    "tid": s.name.split(".")[0],
                    "args": {
                        "trace_id": s.trace_id,
                        "span_id": s.span_id,
                        "parent_id": s.parent_id,
                        **s.attrs,
                    },
                })
        total = len(out)
        truncated = cap >= 0 and total > cap
        if truncated:
            out.sort(key=lambda e: e["ts"])
            out = out[:cap]
        return {"events": out, "truncated": truncated, "total_spans": total}


_RECORDER = SpanRecorder()
for _name in TIMELINE_NAMES:
    _RECORDER.keep_timeline(_name, clocks=_name in CLOCKED_NAMES)


def get_recorder() -> SpanRecorder:
    return _RECORDER


@contextlib.contextmanager
def span(name: str, attrs: Optional[dict] = None,
         recorder: Optional[SpanRecorder] = None):
    """Record a span around a block, propagating the contextvar: the
    block runs under a child context, so nested spans (and anything that
    serializes the ambient context into an envelope) chain correctly.
    Yields the child TraceContext."""
    parent = trace_context.current()
    ctx = parent.child() if parent is not None else trace_context.new_context()
    token = trace_context.attach(ctx)
    t0 = time.time()
    status = "ok"
    try:
        yield ctx
    except BaseException:
        status = "error"
        raise
    finally:
        try:
            trace_context.detach(token)
        except ValueError:
            # unwound in a different Context (async-generator finalized
            # by the loop in a fresh task); still record the span below
            pass
        rec = recorder if recorder is not None else _RECORDER
        rec.add(Span(
            trace_id=ctx.trace_id,
            span_id=ctx.span_id,
            parent_id=parent.span_id if parent is not None else None,
            name=name,
            start=t0,
            end=time.time(),
            attrs=dict(attrs or {}),
            status=status,
        ))


# -- layer spans ---------------------------------------------------------------

CLOCK_MARKER = "obs.clock:"  # + time.time_ns() at the marker's start


def _annotation(name: str):
    """The profiler's host annotation for ``name``, or None in a process
    that has not imported jax: no profiler session can be running there,
    and core/, cluster/ and serve/ import obs without wanting jax. With
    jax loaded and no session running this costs well under a
    microsecond."""
    jax = sys.modules.get("jax")
    try:
        return jax.profiler.TraceAnnotation(name) if jax is not None else None
    except AttributeError:  # another thread is halfway through importing jax
        return None


def _clock_marker() -> None:
    """Write one annotation whose name carries the recorder's clock
    (``time.time_ns()``) at the instant the annotation starts on the
    profiler's. ``capture()`` writes one at each edge."""
    ann = _annotation(f"{CLOCK_MARKER}{time.time_ns()}")
    if ann is not None:
        with ann:
            pass


def clock_offset(host_events) -> Optional[float]:
    """Seconds to ADD to a recorder time (time.time()) to get the same
    instant on the profiler's clock, from the clock markers among
    ``host_events`` (an iterable of (name, start_s) in a trace); None
    when the trace holds no marker. The median over the markers found."""
    offsets = sorted(
        start_s - int(name[len(CLOCK_MARKER):]) * 1e-9
        for name, start_s in host_events if name.startswith(CLOCK_MARKER)
    )
    return offsets[len(offsets) // 2] if offsets else None


class LayerSpan:
    """One use of ``layer_span``: a context manager. ``attrs`` may be
    filled inside the block (a lock wait is known only once the lock is
    held); they reach the recorded ``Span``, not the profiler's event."""

    __slots__ = ("name", "attrs", "start", "_ctx", "_rec", "_ann", "_ids", "_clocks")

    def __init__(self, name: str, ctx, attrs: Optional[dict],
                 rec: SpanRecorder):
        self.name = name
        self.attrs = attrs if attrs is not None else {}
        self.start = 0.0
        self._ctx = ctx
        self._rec = rec
        self._ann = None
        self._ids = None  # (trace_id, span_id, parent_id) while captured
        self._clocks = None  # host_clocks() at entry, for a clocked timeline name

    def __enter__(self) -> "LayerSpan":
        rec = self._rec
        local = rec._layer_local
        stack = getattr(local, "stack", None)
        if stack is None:
            stack = local.stack = []
        if rec._captures:
            outer = stack[-1]._ids if stack else None
            if self._ctx is not None:
                trace_id, parent = self._ctx.trace_id, self._ctx.span_id
            elif outer is not None:
                trace_id, parent = outer[0], outer[1]
            else:
                trace_id, parent = "layers", None
            self._ids = (trace_id, trace_context._rand_hex(8), parent)
        stack.append(self)
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        if self.name in rec._clocked:
            self._clocks = host_clocks()
        self.start = time.time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end = time.time()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._rec._layer_local.stack
        if stack and stack[-1] is self:
            stack.pop()
        self._rec._layer_done(
            self.name, self.start, end, self._ids, self.attrs,
            "ok" if exc_type is None else "error", self._clocks,
        )


def layer_span(name: str, ctx: Optional[trace_context.TraceContext] = None,
               attrs: Optional[dict] = None,
               recorder: Optional[SpanRecorder] = None) -> LayerSpan:
    """A span of a LAYER's work, recorded where the work happens:

        with obs.layer_span("engine.step") as sp:
            ...
            sp.attrs["rows"] = n

    Always: an annotation of that name in the profiler's trace, and
    count, busy seconds and the longest use under
    ``layer_counters()[name]``; for a timeline name (``TIMELINE_NAMES``)
    also ``(start, end, extra)`` in ``layer_timeline(name)``. While a
    ``capture()`` is open also a ``Span`` in the recorder's layer ring,
    whose parent is ``ctx`` (work done for one request: that request's
    TraceContext) or else the layer span enclosing it on this thread."""
    return LayerSpan(name, ctx, attrs, recorder if recorder is not None else _RECORDER)


def layer_record(name: str, start: float, end: Optional[float] = None,
                 attrs: Optional[dict] = None,
                 recorder: Optional[SpanRecorder] = None) -> None:
    """A layer span whose start was stamped elsewhere (another thread or
    process, as ``time.time()``) and which ends now: counted, and
    captured while a capture is open, like any other. The profiler
    cannot be handed a finished event, so it is not in its trace."""
    rec = recorder if recorder is not None else _RECORDER
    ids = ("layers", trace_context._rand_hex(8), None) if rec._captures else None
    rec._layer_done(name, start, time.time() if end is None else end, ids, attrs)


# -- the host's clocks and the collector's pauses -----------------------------


def host_clocks() -> tuple:
    """(thread id, this thread's CPU seconds, the process's CPU seconds,
    this thread's involuntary context switches or None, the collector's
    seconds so far): what a clocked timeline name takes at entry. Over
    the interval between two entries of one thread the differences say
    how long that thread ran, how long the process's OTHER threads ran
    (process less thread), whether the kernel took the thread off a core,
    and how long the collector held everything. A few microseconds."""
    switches = None
    if _RUSAGE_THREAD is not None:
        switches = resource.getrusage(_RUSAGE_THREAD).ru_nivcsw
    cell = _RECORDER._layer_counts.get(GC_NAME)
    return (threading.get_ident(), time.thread_time(), time.process_time(), switches,
            cell[1] if cell is not None else 0.0)


_GC_STARTED = [0.0]


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks hook: every collection adds to the layer counter
    ``host.gc``; one of generation 1 or 2, or any longer than GC_KEEP_S,
    is also kept in that name's timeline with its generation. The
    interpreter runs one collection at a time and none inside this hook,
    and the collection may have begun inside ``_layer_done`` on this very
    thread, so nothing here takes the recorder's lock: one writer, and
    readers that take none either."""
    if phase == "start":
        _GC_STARTED[0] = time.time()
        return
    start, end = _GC_STARTED[0], time.time()
    _RECORDER._count(GC_NAME, start, end)
    generation = info.get("generation", 0)
    if generation or end - start > GC_KEEP_S:
        _RECORDER._keep(GC_NAME, (start, end, {"generation": generation}))


def watch_gc() -> None:
    """Install the collector's hook, once however often this is called
    (``ray_tpu.init()``, a train worker entering its loop). It only
    watches: nothing here rests, freezes or tunes the collector."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
        from ray_tpu.util.metrics import register_collector

        register_collector(_export_layer_counters)


def unwatch_gc() -> None:
    """Remove the hook (``ray_tpu.shutdown()``); ``host.gc``'s counter
    and timeline stay, as every layer counter outlives the runtime."""
    while _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def _export_layer_counters() -> None:
    """util/metrics collector: mirror the layer counters into the
    registry (``/metrics``) when it is read, not on every span."""
    from ray_tpu.util.metrics import Counter, Gauge

    counts = _RECORDER.layer_counters()
    n = Counter("obs_layer_spans_total",
                description="layer spans ended, by span name", tag_keys=("name",))
    busy = Counter("obs_layer_busy_seconds_total",
                   description="seconds inside layer spans, by span name",
                   tag_keys=("name",))
    longest = Gauge("obs_layer_max_seconds",
                    description="the longest single layer span, by span name",
                    tag_keys=("name",))
    for name, c in counts.items():
        n.set_total(c["count"], {"name": name})
        busy.set_total(c["busy_s"], {"name": name})
        longest.set(c["max_s"], {"name": name})


register_metrics = _export_layer_counters  # scripts/check_metrics.py hook
