"""Capture ledger: one envelope for every checked-in bench capture.

Every `benchmarks/*_r*.json` capture historically had its own shape, no
hardware fingerprint, and only ad-hoc per-file tier-1 gates — so the
perf trajectory was unreadable by machines, and the "refresh every CPU
capture on the TPU" carry-over had no mechanical definition of *refresh*
(reference discipline: the MLPerf-on-TPU-pods capture format — every
number stamped with the hardware that produced it, comparable only to
its own kind).

The envelope is ADDITIVE: the original capture payload keeps its
top-level keys (every existing reader — tests, benches, humans — keeps
working) and gains ONE reserved key::

    {
      ...original payload...,
      "perfwatch": {
        "schema": 1,
        "bench": "profile_trainstep",      # capture family
        "rev": "r06",                      # capture revision
        "captured_at": "2026-08-07T00:00:00Z",
        "fingerprint": {                   # hardware identity; null = unknown
          "device_kind": "cpu", "platform": "cpu",
          "device_count": 1, "jax_version": "0.4.37",
        },
        "metrics": {                       # the machine-comparable numbers
          "coverage_pct": {"value": 97.4, "unit": "%",
                            "better": "higher", "rel_tol": 0.1},
        },
      },
    }

Comparability contract (ray_tpu/analysis/perf_gate.py enforces it):
captures compare ONLY against the most recent ledger entry of the same
bench family with a MATCHING fingerprint; a ``null`` fingerprint field
is a wildcard (legacy captures predate the envelope and recorded no jax
version). A fresh TPU capture therefore never fights a CPU baseline —
it records as the new baseline for its own fingerprint, which is
exactly how a TPU refresh supersedes a CPU number.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Optional

SCHEMA_VERSION = 1
ENVELOPE_KEY = "perfwatch"

FINGERPRINT_KEYS = ("device_kind", "platform", "device_count", "jax_version")

BETTER_HIGHER = "higher"
BETTER_LOWER = "lower"
VALID_BETTER = frozenset({BETTER_HIGHER, BETTER_LOWER})

# Default relative tolerance bands. Wall-clock numbers on a shared CPU
# runner are noisy (the tier-1 suite runs under load), so time-like
# metrics get a wide band; ratios/coverages are stable and get a tight
# one. Individual captures override per metric.
DEFAULT_REL_TOL = 0.5


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def default_ledger_dir() -> str:
    return os.path.join(_repo_root(), "benchmarks")


@dataclasses.dataclass
class MetricSpec:
    """One comparable number + its tolerance band."""

    value: float
    unit: str = ""
    better: str = BETTER_HIGHER
    rel_tol: float = DEFAULT_REL_TOL
    abs_tol: float = 0.0

    def to_dict(self) -> dict:
        return {
            "value": self.value, "unit": self.unit, "better": self.better,
            "rel_tol": self.rel_tol, "abs_tol": self.abs_tol,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "MetricSpec":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def metric(value, unit: str = "", better: str = BETTER_HIGHER,
           rel_tol: float = DEFAULT_REL_TOL, abs_tol: float = 0.0) -> dict:
    """Shorthand the bench writers use to declare one enveloped metric."""
    if better not in VALID_BETTER:
        raise ValueError(f"better must be one of {sorted(VALID_BETTER)}")
    return MetricSpec(float(value), unit, better, rel_tol, abs_tol).to_dict()


def current_fingerprint() -> dict:
    """Hardware fingerprint of THIS process's JAX backend.

    Importing jax here initializes a backend — only call from a process
    that is allowed to hold the device (never a JAX-free parent)."""
    import jax

    dev = jax.devices()[0]
    return {
        "device_kind": getattr(dev, "device_kind", "") or dev.platform,
        "platform": dev.platform,
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
    }


def fingerprints_match(a: Optional[dict], b: Optional[dict]) -> bool:
    """Same-hardware test with null-as-wildcard: legacy captures recorded
    no jax version (the envelope postdates them), and an unknown field
    must not make every legacy baseline unreachable."""
    if not a or not b:
        return False
    for k in FINGERPRINT_KEYS:
        va, vb = a.get(k), b.get(k)
        if va is None or vb is None:
            continue
        if va != vb:
            return False
    return True


def envelope_of(doc: dict) -> Optional[dict]:
    env = doc.get(ENVELOPE_KEY) if isinstance(doc, dict) else None
    return env if isinstance(env, dict) else None


def payload_of(doc: dict) -> dict:
    """The original capture payload, envelope key stripped."""
    return {k: v for k, v in doc.items() if k != ENVELOPE_KEY}


def wrap(payload: dict, *, bench: str, rev: str, metrics: dict,
         fingerprint: Optional[dict] = None,
         captured_at: Optional[str] = None) -> dict:
    """Envelope a capture payload (additive: payload keys preserved)."""
    if not isinstance(payload, dict):
        raise TypeError(f"capture payload must be a dict, got {type(payload)}")
    fp = {k: (fingerprint or {}).get(k) for k in FINGERPRINT_KEYS}
    norm_metrics = {}
    for name, spec in (metrics or {}).items():
        if isinstance(spec, MetricSpec):
            spec = spec.to_dict()
        norm_metrics[name] = MetricSpec.from_dict(spec).to_dict()
    return {
        **payload_of(payload),
        ENVELOPE_KEY: {
            "schema": SCHEMA_VERSION,
            "bench": bench,
            "rev": rev,
            "captured_at": captured_at or time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "fingerprint": fp,
            "metrics": norm_metrics,
        },
    }


def validate_envelope(doc: dict) -> list[str]:
    """Schema problems of one enveloped capture (empty = valid)."""
    problems = []
    env = envelope_of(doc)
    if env is None:
        return ["no perfwatch envelope"]
    if env.get("schema") != SCHEMA_VERSION:
        problems.append(f"unknown envelope schema {env.get('schema')!r}")
    for field in ("bench", "rev", "captured_at"):
        if not isinstance(env.get(field), str) or not env.get(field):
            problems.append(f"envelope field {field!r} missing or not a string")
    fp = env.get("fingerprint")
    if not isinstance(fp, dict):
        problems.append("envelope fingerprint missing")
    else:
        for k in FINGERPRINT_KEYS:
            if k not in fp:
                problems.append(f"fingerprint missing key {k!r}")
    metrics = env.get("metrics")
    if not isinstance(metrics, dict):
        problems.append("envelope metrics missing (may be empty, not absent)")
    else:
        for name, spec in metrics.items():
            if not isinstance(spec, dict):
                problems.append(f"metric {name!r}: not a dict")
                continue
            v = spec.get("value")
            if not isinstance(v, (int, float)) or v != v:  # NaN check
                problems.append(f"metric {name!r}: non-numeric value {v!r}")
            if spec.get("better") not in VALID_BETTER:
                problems.append(
                    f"metric {name!r}: better={spec.get('better')!r} not in "
                    f"{sorted(VALID_BETTER)}"
                )
            for tol in ("rel_tol", "abs_tol"):
                t = spec.get(tol, 0)
                if not isinstance(t, (int, float)) or t < 0:
                    problems.append(f"metric {name!r}: invalid {tol}={t!r}")
    return problems


class CaptureLedger:
    """Reader/writer over the capture directory (default: benchmarks/).

    The ledger IS the directory: one enveloped JSON per capture, history
    in git. ``write`` envelopes + persists; ``entries``/``baseline_for``
    resolve comparison baselines by (bench family, fingerprint)."""

    def __init__(self, root: Optional[str] = None):
        self.root = root or default_ledger_dir()

    # -- writing --------------------------------------------------------------

    def write(self, name_or_path: str, payload: dict, *, bench: str,
              rev: str, metrics: dict,
              fingerprint: Optional[dict] = None) -> str:
        """Envelope + write a capture. ``name_or_path`` may be a bare
        filename (lands in the ledger root) or a full path (the bench's
        --out flag wins, wherever it points)."""
        path = (name_or_path if os.path.isabs(name_or_path)
                or os.sep in name_or_path
                else os.path.join(self.root, name_or_path))
        doc = wrap(payload, bench=bench, rev=rev, metrics=metrics,
                   fingerprint=fingerprint)
        with open(path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        return path

    # -- reading --------------------------------------------------------------

    def entries(self, bench: Optional[str] = None) -> list[tuple[str, dict]]:
        """(path, doc) for every enveloped capture in the ledger,
        newest-first by captured_at. Un-enveloped JSONs are skipped here
        (check_perf flags them as migration gaps)."""
        out = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return []
        for name in names:
            if not name.endswith(".json"):
                continue
            path = os.path.join(self.root, name)
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue
            env = envelope_of(doc)
            if env is None:
                continue
            if bench is not None and env.get("bench") != bench:
                continue
            out.append((path, doc))
        out.sort(key=lambda pd: envelope_of(pd[1]).get("captured_at", ""),
                 reverse=True)
        return out

    def unenveloped(self) -> list[str]:
        """Capture files the migration has not covered (ledger-integrity
        problem list for check_perf)."""
        out = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return []
        for name in names:
            if not name.endswith(".json"):
                continue
            path = os.path.join(self.root, name)
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, json.JSONDecodeError):
                out.append(path)
                continue
            if not isinstance(doc, dict) or envelope_of(doc) is None:
                out.append(path)
        return out

    def baseline_for(self, bench: str, fingerprint: Optional[dict], *,
                     exclude: Optional[str] = None
                     ) -> Optional[tuple[str, dict]]:
        """Most recent same-fingerprint entry of ``bench`` — the capture
        a fresh run is gated against. ``exclude`` drops one path (the
        fresh capture itself when it already landed in the ledger)."""
        for path, doc in self.entries(bench):
            if exclude is not None and os.path.abspath(path) == os.path.abspath(exclude):
                continue
            if fingerprints_match(envelope_of(doc).get("fingerprint"),
                                  fingerprint):
                return path, doc
        return None


def load_capture(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def write_capture(path: str, payload: dict, *, bench: str, rev: str,
                  metrics: dict, fingerprint: Optional[dict] = None,
                  fingerprint_fn: Optional[Callable[[], dict]] = None) -> str:
    """Module-level convenience the bench scripts call in place of their
    old ``json.dump``: envelope + write to ``path``. ``fingerprint_fn``
    defaults to ``current_fingerprint`` guarded — a bench that never
    initialized a backend still writes a valid (wildcard) envelope."""
    if fingerprint is None:
        fn = fingerprint_fn or current_fingerprint
        try:
            fingerprint = fn()
        except Exception:  # noqa: BLE001 — no backend: wildcard fingerprint
            fingerprint = None
    return CaptureLedger(os.path.dirname(os.path.abspath(path))).write(
        path, payload, bench=bench, rev=rev, metrics=metrics,
        fingerprint=fingerprint,
    )
