"""User-facing metrics: Counter / Gauge / Histogram + Prometheus export.

Reference analogs: python/ray/util/metrics.py (the user API) and the
node metrics agent pipeline (C++ opencensus -> _private/metrics_agent.py
-> Prometheus exposition). Single-host collapse: one process-wide
registry rendering Prometheus text directly (served by
ray_tpu.dashboard); no agent hop.
"""

from __future__ import annotations

import itertools
import threading
import time
import uuid
from bisect import bisect_right
from typing import Callable, Optional, Sequence

_REGISTRY_LOCK = threading.Lock()
_REGISTRY: dict[str, "Metric"] = {}

# Process-epoch id: a restarted process re-registers every counter at 0.
# Snapshots carry this id so a consumer (the ray_tpu.obs.telemetry plane)
# can tell "the counter went backwards" (impossible) from "the process
# restarted" (totals from the dead epoch are banked, the new epoch counts
# from zero — never a negative or double-counted delta).
PROCESS_EPOCH = uuid.uuid4().hex[:12]

# Monotonic per-process snapshot sequence: lets a consumer ignore a
# delayed/re-ordered snapshot without comparing wall clocks.
_SNAPSHOT_SEQ = itertools.count(1)

DEFAULT_HISTOGRAM_BOUNDARIES = [
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 50, 100,
]


def _fq(name: str) -> str:
    return name if name.startswith("ray_tpu_") else f"ray_tpu_{name}"


class Metric:
    """Base: named metric with optional tag keys; one time series per
    observed tag-value combination."""

    TYPE = "untyped"

    def __init__(
        self,
        name: str,
        description: str = "",
        tag_keys: Optional[Sequence[str]] = None,
    ):
        if not name:
            raise ValueError("metric name required")
        self.name = _fq(name)
        self.description = description
        self.tag_keys = tuple(tag_keys or ())
        self._default_tags: dict = {}
        self._series: dict[tuple, float] = {}
        self._lock = threading.Lock()
        with _REGISTRY_LOCK:
            existing = _REGISTRY.get(self.name)
            if existing is not None:
                if existing.TYPE != self.TYPE:
                    raise ValueError(
                        f"metric {self.name!r} already registered as {existing.TYPE}"
                    )
                # same name+type: SHARE storage so every instance's records
                # land in the one exported time series (silently shadowing
                # would lose the first instance's counts)
                self._series = existing._series
                self._lock = existing._lock
                if isinstance(existing, Histogram) and isinstance(self, Histogram):
                    self._buckets = existing._buckets
                    self._sums = existing._sums
                    self._counts = existing._counts
                    self.boundaries = existing.boundaries
                return
            _REGISTRY[self.name] = self

    def set_default_tags(self, tags: dict) -> "Metric":
        unknown = set(tags) - set(self.tag_keys)
        if unknown:
            raise ValueError(f"unknown tag keys: {sorted(unknown)}")
        self._default_tags = dict(tags)
        return self

    def _key(self, tags: Optional[dict]) -> tuple:
        merged = {**self._default_tags, **(tags or {})}
        unknown = set(merged) - set(self.tag_keys)
        if unknown:
            raise ValueError(f"unknown tag keys: {sorted(unknown)}")
        return tuple(merged.get(k, "") for k in self.tag_keys)

    # subclasses implement record semantics over self._series

    def series(self) -> dict[tuple, float]:
        with self._lock:
            return dict(self._series)

    def remove_series(self, tags: Optional[dict] = None) -> None:
        """Retract one tag combination entirely. Without this, a gauge
        for a deleted entity (replica pool, reporter) keeps exporting its
        last value forever — downstream sum rollups then count phantoms."""
        k = self._key(tags)
        with self._lock:
            self._series.pop(k, None)
            if isinstance(self, Histogram):
                self._buckets.pop(k, None)
                self._sums.pop(k, None)
                self._counts.pop(k, None)


class Counter(Metric):
    TYPE = "counter"

    def inc(self, value: float = 1.0, tags: Optional[dict] = None) -> None:
        if value < 0:
            raise ValueError("counters only increase")
        k = self._key(tags)
        with self._lock:
            self._series[k] = self._series.get(k, 0.0) + value

    def set_total(self, value: float, tags: Optional[dict] = None) -> None:
        """Mirror a monotonic total that is kept elsewhere (a collector's
        write); the series never goes down."""
        k = self._key(tags)
        with self._lock:
            self._series[k] = max(self._series.get(k, 0.0), float(value))


class Gauge(Metric):
    TYPE = "gauge"

    def set(self, value: float, tags: Optional[dict] = None) -> None:
        with self._lock:
            self._series[self._key(tags)] = float(value)

    def inc(self, value: float = 1.0, tags: Optional[dict] = None) -> None:
        k = self._key(tags)
        with self._lock:
            self._series[k] = self._series.get(k, 0.0) + value

    def dec(self, value: float = 1.0, tags: Optional[dict] = None) -> None:
        self.inc(-value, tags)


class Histogram(Metric):
    TYPE = "histogram"

    def __init__(
        self,
        name: str,
        description: str = "",
        boundaries: Optional[Sequence[float]] = None,
        tag_keys: Optional[Sequence[str]] = None,
    ):
        # set BEFORE super().__init__: the base class's same-name sharing
        # branch replaces these with the registered instance's storage —
        # assigning after it would clobber the share and this instance
        # would read/write a private empty histogram
        self.boundaries = sorted(boundaries or DEFAULT_HISTOGRAM_BOUNDARIES)
        self._buckets: dict[tuple, list] = {}
        self._sums: dict[tuple, float] = {}
        self._counts: dict[tuple, int] = {}
        super().__init__(name, description, tag_keys)

    def observe(self, value: float, tags: Optional[dict] = None) -> None:
        k = self._key(tags)
        with self._lock:
            buckets = self._buckets.setdefault(k, [0] * (len(self.boundaries) + 1))
            buckets[bisect_right(self.boundaries, value)] += 1
            self._sums[k] = self._sums.get(k, 0.0) + value
            self._counts[k] = self._counts.get(k, 0) + 1

    def hist_data(self) -> dict:
        with self._lock:
            return {
                k: (list(b), self._sums.get(k, 0.0), self._counts.get(k, 0))
                for k, b in self._buckets.items()
            }


# Collectors: callables run before every read of the registry, for
# totals that are cheaper to keep outside it (a plain int on a hot path)
# and mirror in when someone looks. Code, not data: clear_registry()
# leaves them.
_COLLECTORS: list[Callable[[], None]] = []


def register_collector(fn: Callable[[], None]) -> None:
    with _REGISTRY_LOCK:
        if fn not in _COLLECTORS:
            _COLLECTORS.append(fn)


def registry_snapshot() -> list[Metric]:
    with _REGISTRY_LOCK:
        collectors = list(_COLLECTORS)
    for fn in collectors:
        try:
            fn()
        except Exception:  # noqa: BLE001 - a broken collector must not break /metrics
            pass
    with _REGISTRY_LOCK:
        return list(_REGISTRY.values())


def snapshot_meta() -> dict:
    """Timestamp + epoch header every serialized snapshot carries.

    ``ts_monotonic`` orders snapshots from ONE process; ``ts_wall`` places
    them on the cluster timeline; ``epoch`` detects process restarts
    (counter resets); ``seq`` detects re-ordered/duplicated deliveries."""
    return {
        "epoch": PROCESS_EPOCH,
        "seq": next(_SNAPSHOT_SEQ),
        "ts_monotonic": time.monotonic(),
        "ts_wall": time.time(),
    }


def snapshot_registry(
    series_filter: Optional[Callable[[str, dict], bool]] = None,
) -> dict:
    """Serializable point-in-time snapshot of the whole registry.

    Counters ship as monotonic totals (not deltas) and histograms as full
    bucket vectors: a consumer that misses N snapshots loses freshness,
    never counts — re-sends can only be ignored (by ``seq``) or replace
    state, so drops/delays are staleness, not corruption.

    ``series_filter(name, tags_dict) -> bool`` narrows the snapshot (a
    node daemon colocated with other subsystems ships only the series it
    owns)."""
    out = snapshot_meta()
    out["metrics"] = []
    for m in registry_snapshot():
        entry: dict = {
            "name": m.name,
            "type": m.TYPE,
            "description": m.description,
            "tag_keys": list(m.tag_keys),
        }
        series: list[dict] = []
        if isinstance(m, Histogram):
            entry["boundaries"] = list(m.boundaries)
            for k, (buckets, total, count) in m.hist_data().items():
                tags = dict(zip(m.tag_keys, k))
                if series_filter is not None and not series_filter(m.name, tags):
                    continue
                series.append({
                    "tags": list(k), "buckets": list(buckets),
                    "sum": total, "count": count,
                })
        else:
            for k, v in m.series().items():
                tags = dict(zip(m.tag_keys, k))
                if series_filter is not None and not series_filter(m.name, tags):
                    continue
                series.append({"tags": list(k), "value": v})
        if series:
            entry["series"] = series
            out["metrics"].append(entry)
    return out


def clear_registry() -> None:
    """Test hook."""
    with _REGISTRY_LOCK:
        _REGISTRY.clear()


def _escape_label_value(v) -> str:
    """Prometheus exposition escaping for label values: backslash, double
    quote, and newline must be escaped or one prompt/path-derived tag
    value corrupts every line after it in the scrape."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_tags(keys: Sequence[str], vals: tuple, extra: str = "") -> str:
    # empty values are emitted explicitly (`k=""`): dropping them made a
    # series tagged {model: ""} collide with an untagged sibling series
    parts = [f'{k}="{_escape_label_value(v)}"' for k, v in zip(keys, vals)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def prometheus_text() -> str:
    """Render the whole registry in Prometheus exposition format
    (reference: metrics_agent.py's opencensus->Prometheus conversion)."""
    lines = []
    for m in registry_snapshot():
        lines.append(f"# HELP {m.name} {m.description}")
        lines.append(f"# TYPE {m.name} {m.TYPE}")
        if isinstance(m, Histogram):
            for k, (buckets, total, count) in m.hist_data().items():
                cum = 0
                for b, n in zip(m.boundaries, buckets):
                    cum += n
                    le = f'le="{b}"'
                    lines.append(
                        f"{m.name}_bucket"
                        f"{_fmt_tags(m.tag_keys, k, le)} {cum}"
                    )
                cum += buckets[-1]
                le_inf = 'le="+Inf"'
                lines.append(
                    f"{m.name}_bucket"
                    f"{_fmt_tags(m.tag_keys, k, le_inf)} {cum}"
                )
                lines.append(f"{m.name}_sum{_fmt_tags(m.tag_keys, k)} {total}")
                lines.append(f"{m.name}_count{_fmt_tags(m.tag_keys, k)} {count}")
        else:
            for k, v in m.series().items():
                lines.append(f"{m.name}{_fmt_tags(m.tag_keys, k)} {v}")
    return "\n".join(lines) + "\n"
