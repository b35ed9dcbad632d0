"""ray_tpu.obs.perfwatch — the capture ledger and its regression gates.

ledger.py, migrate.py and ray_tpu/analysis/perf_gate.py: every bench
capture carries one additive envelope — schema version, hardware
fingerprint, metric dict with tolerance bands — and
``scripts/check_perf.py`` gates fresh captures against the most recent
same-fingerprint baseline.
"""

from __future__ import annotations

from typing import Optional

from ray_tpu.obs.perfwatch.ledger import (
    CaptureLedger,
    MetricSpec,
    current_fingerprint,
    envelope_of,
    fingerprints_match,
    load_capture,
    metric,
    payload_of,
    validate_envelope,
    wrap,
    write_capture,
)

__all__ = [
    "CaptureLedger",
    "MetricSpec",
    "current_fingerprint",
    "envelope_of",
    "fingerprints_match",
    "load_capture",
    "metric",
    "payload_of",
    "save_capture",
    "validate_envelope",
    "wrap",
    "write_capture",
]


def save_capture(path: str, payload: dict, *,
                 metrics: Optional[dict] = None,
                 fingerprint: Optional[dict] = None) -> str:
    """The one-call writer the bench scripts use in place of their old
    ``json.dump``: derives the bench family + revision from the
    filename, derives comparable metrics from the payload's shape (same
    derivation the migration applied to the legacy captures, so fresh
    captures stay comparable to their migrated baselines), stamps the
    current backend's fingerprint (wildcard when no backend is up), and
    writes the enveloped capture."""
    from ray_tpu.obs.perfwatch.migrate import (
        bench_rev_from_name,
        derive_metrics,
    )

    bench, rev = bench_rev_from_name(path)
    if metrics is None:
        metrics = derive_metrics(payload)
    return write_capture(path, payload, bench=bench, rev=rev,
                         metrics=metrics, fingerprint=fingerprint)
