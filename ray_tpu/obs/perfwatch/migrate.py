"""One-shot (idempotent) migration of pre-envelope captures into the
capture ledger.

Two jobs:

 * wrap every ``benchmarks/*.json`` capture in the perfwatch envelope
   IN PLACE (additive — payload keys survive, existing readers keep
   working), deriving comparable metrics + a best-effort hardware
   fingerprint from each known legacy shape;
 * end the capture-location split: root-level ``BENCH_r*.json`` /
   ``PERF_r*.json`` / ``MULTICHIP_r*.json`` move into ``benchmarks/``
   (enveloped), with a symlink left at the old root path so any reader
   of the old location keeps working.

Run: ``python -m ray_tpu.obs.perfwatch.migrate`` (safe to re-run: files
already carrying an envelope, and root paths already symlinks, are
skipped).
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Optional

from ray_tpu.obs.perfwatch.ledger import (
    ENVELOPE_KEY,
    envelope_of,
    metric,
    wrap,
)

_REV_RE = re.compile(r"^(?P<bench>.+?)_(?P<rev>r\d+)$")

# root-level captures that move under benchmarks/ (satellite: end the
# two-directory split)
_ROOT_CAPTURE_RE = re.compile(r"^(BENCH|PERF|MULTICHIP)_r\d+\.json$")

# tolerance bands by metric character: wall-clock numbers on a loaded
# shared-CPU runner swing hard, ratios and coverages don't
REL_TIME = 1.0
REL_THROUGHPUT = 0.6
REL_RATIO = 0.25
REL_COVERAGE = 0.08


def bench_rev_from_name(filename: str) -> tuple[str, str]:
    stem = os.path.splitext(os.path.basename(filename))[0]
    m = _REV_RE.match(stem)
    if m:
        return m.group("bench"), m.group("rev")
    return stem, "r00"


def _iso(ts: float) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(ts))


def fingerprint_from_payload(payload: dict) -> dict:
    """Best-effort fingerprint for a capture that predates the envelope.
    Unknown fields stay None — a WILDCARD, never a guess (a fabricated
    jax version would manufacture comparability that doesn't exist)."""
    parsed = payload.get("parsed")
    nested = parsed if isinstance(parsed, dict) else {}
    device_kind = (payload.get("device_kind") or payload.get("device")
                   or nested.get("device"))
    platform = payload.get("platform") or nested.get("platform")
    if platform is None and isinstance(device_kind, str):
        low = device_kind.lower()
        if "tpu" in low or low.startswith("v5") or low.startswith("v6"):
            platform = "tpu"
        elif low == "cpu":
            platform = "cpu"
    count = payload.get("n_devices") or payload.get("num_devices")
    return {
        "device_kind": device_kind if isinstance(device_kind, str) else None,
        "platform": platform if isinstance(platform, str) else None,
        "device_count": count if isinstance(count, int) else None,
        "jax_version": None,
    }


def _gate_metrics(payload: dict) -> dict:
    """Boolean gates -> 0/1 metrics with a zero band: a gate that was
    green may never silently go red."""
    out = {}
    for key in ("gate", "gates"):
        gates = payload.get(key)
        if isinstance(gates, dict):
            for name, v in gates.items():
                if isinstance(v, bool):
                    out[f"gate_{name}"] = metric(
                        1.0 if v else 0.0, "bool", rel_tol=0.0)
    for key in ("token_identical", "all_gates_pass", "ok", "exact"):
        v = payload.get(key)
        if isinstance(v, bool):
            out[f"gate_{key}"] = metric(1.0 if v else 0.0, "bool", rel_tol=0.0)
    return out


def derive_metrics(payload: dict) -> dict:
    """Comparable numbers from a legacy capture's known shapes."""
    out: dict = {}

    # headline {metric, value, unit} records (SERVING, SPEC, KVTIER, ...)
    name = payload.get("metric")
    value = payload.get("value")
    if isinstance(name, str) and isinstance(value, (int, float)) \
            and not isinstance(value, bool):
        out[name] = metric(value, str(payload.get("unit", "")),
                           rel_tol=REL_THROUGHPUT)

    # profiler StepProfile captures
    if isinstance(payload.get("coverage_pct"), (int, float)):
        out["coverage_pct"] = metric(payload["coverage_pct"], "%",
                                     rel_tol=REL_COVERAGE)
    if isinstance(payload.get("measured_step_ms"), (int, float)):
        out["measured_step_ms"] = metric(payload["measured_step_ms"], "ms",
                                         better="lower", rel_tol=REL_TIME)

    # driver records ({n, cmd, rc, parsed:{...}})
    parsed = payload.get("parsed")
    if isinstance(parsed, dict) and isinstance(parsed.get("metric"), str) \
            and isinstance(parsed.get("value"), (int, float)):
        out[parsed["metric"]] = metric(
            parsed["value"], str(parsed.get("unit", "")),
            rel_tol=REL_RATIO)
        tps = parsed.get("tokens_per_sec")
        if isinstance(tps, (int, float)):
            out["tokens_per_sec"] = metric(tps, "tok/s",
                                           rel_tol=REL_THROUGHPUT)
    tps = payload.get("tokens_per_sec")
    if isinstance(tps, (int, float)) and not isinstance(tps, bool) \
            and "tokens_per_sec" not in out:
        out["tokens_per_sec"] = metric(tps, "tok/s", rel_tol=REL_THROUGHPUT)

    # microbenchmark suites ({name: {value, unit, ...}}, PERF_r*)
    for k, v in payload.items():
        if isinstance(v, dict) and isinstance(v.get("value"), (int, float)) \
                and isinstance(v.get("unit"), str) and k not in out \
                and k != "parsed":
            out[k] = metric(v["value"], v["unit"], rel_tol=REL_THROUGHPUT)

    # control-plane ingest (CONTROLPLANE_gcs_r20): batched ops/s at the
    # largest node count is THE number item 2's sharding will be graded on
    results = payload.get("results")
    if isinstance(results, list) and results \
            and all(isinstance(r, dict) and "nodes" in r for r in results):
        largest = max(results, key=lambda r: r.get("nodes", 0))
        for key, unit in (("batched_ops_per_s", "ops/s"),
                          ("unbatched_ops_per_s", "ops/s")):
            if isinstance(largest.get(key), (int, float)):
                out[f"{key}_at_{largest['nodes']}_nodes"] = metric(
                    largest[key], unit, rel_tol=REL_THROUGHPUT)

    out.update(_gate_metrics(payload))
    return out


def migrate_file(path: str) -> Optional[str]:
    """Envelope one capture file in place; returns an action string or
    None when the file already carries an envelope / isn't a capture."""
    with open(path) as f:
        payload = json.load(f)
    if not isinstance(payload, dict) or envelope_of(payload) is not None:
        return None
    bench, rev = bench_rev_from_name(path)
    ts = payload.get("ts")
    if isinstance(ts, str):
        captured_at = ts
    elif isinstance(payload.get("unix_time"), (int, float)):
        captured_at = _iso(payload["unix_time"])
    else:
        captured_at = _iso(os.path.getmtime(path))
    doc = wrap(
        payload, bench=bench, rev=rev, metrics=derive_metrics(payload),
        fingerprint=fingerprint_from_payload(payload),
        captured_at=captured_at,
    )
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    n = len(doc[ENVELOPE_KEY]["metrics"])
    return f"enveloped {path} (bench={bench} rev={rev}, {n} metrics)"


def migrate_root_captures(repo_root: str, bench_dir: str) -> list[str]:
    """Move root BENCH/PERF/MULTICHIP captures into benchmarks/ and leave
    symlink shims at the old paths."""
    actions = []
    for name in sorted(os.listdir(repo_root)):
        if not _ROOT_CAPTURE_RE.match(name):
            continue
        src = os.path.join(repo_root, name)
        dst = os.path.join(bench_dir, name)
        if os.path.islink(src):
            continue  # already migrated
        if os.path.exists(dst):
            actions.append(f"SKIP {src}: {dst} already exists")
            continue
        os.rename(src, dst)
        # relative symlink so the repo stays relocatable
        os.symlink(os.path.join("benchmarks", name), src)
        actions.append(f"moved {name} -> benchmarks/ (symlink shim at root)")
    return actions


def migrate_all(repo_root: Optional[str] = None) -> list[str]:
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    bench_dir = os.path.join(repo_root, "benchmarks")
    actions = migrate_root_captures(repo_root, bench_dir)
    for name in sorted(os.listdir(bench_dir)):
        if not name.endswith(".json"):
            continue
        act = migrate_file(os.path.join(bench_dir, name))
        if act:
            actions.append(act)
    return actions


def main() -> int:
    actions = migrate_all()
    for a in actions:
        print(a)
    print(f"migrate: {len(actions)} action(s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
