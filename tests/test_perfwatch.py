"""obs.perfwatch: capture ledger + regression gates.

Covers the r22 acceptance surface that doesn't need a bench run:
tolerance-band math in both directions, the three gate verdicts
(pass / record-on-fingerprint-mismatch / record-on-missing-baseline),
a synthetic regression failing WITH the offending metric named, the
envelope round-trip of a migrated legacy capture, and the repo ledger
passing run_check (the tier-1 check_perf gate).
"""

from __future__ import annotations

import json
import os

import pytest

from ray_tpu.analysis.perf_gate import (
    FAIL,
    PASS,
    RECORD,
    compare_metric,
    evaluate_capture,
    gate_capture,
    run_check,
)
from ray_tpu.obs.perfwatch import (
    CaptureLedger,
    MetricSpec,
    envelope_of,
    load_capture,
    metric,
    payload_of,
    save_capture,
    validate_envelope,
    wrap,
)

pytestmark = pytest.mark.perfwatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FP_CPU = {"device_kind": "cpu", "platform": "cpu",
          "device_count": 1, "jax_version": "0.4.37"}
FP_TPU = {"device_kind": "TPU v4", "platform": "tpu",
          "device_count": 8, "jax_version": "0.4.37"}


# -- tolerance-band math ------------------------------------------------------


class TestBandMath:
    def test_higher_better_within_band_passes(self):
        base = MetricSpec(100.0, "tok/s", "higher", rel_tol=0.2)
        assert compare_metric("tps", MetricSpec(81.0), base) is None
        assert compare_metric("tps", MetricSpec(250.0), base) is None

    def test_higher_better_regression_below_floor_fails(self):
        base = MetricSpec(100.0, "tok/s", "higher", rel_tol=0.2)
        problem = compare_metric("tps", MetricSpec(79.0), base)
        assert problem is not None
        assert "tps" in problem and "regressed" in problem

    def test_lower_better_regression_above_ceiling_fails(self):
        base = MetricSpec(10.0, "ms", "lower", rel_tol=0.5)
        assert compare_metric("step_ms", MetricSpec(14.9), base) is None
        problem = compare_metric("step_ms", MetricSpec(15.1), base)
        assert problem is not None and "step_ms" in problem

    def test_abs_tol_widens_the_band(self):
        base = MetricSpec(1.0, "ms", "lower", rel_tol=0.0, abs_tol=0.5)
        assert compare_metric("m", MetricSpec(1.4), base) is None
        assert compare_metric("m", MetricSpec(1.6), base) is not None

    def test_baseline_owns_direction(self):
        # a fresh capture flipping `better` cannot relax the gate: the
        # BASELINE spec's direction applies
        base = MetricSpec(100.0, "tok/s", "higher", rel_tol=0.1)
        fresh = MetricSpec(50.0, "tok/s", "lower")
        assert compare_metric("tps", fresh, base) is not None


# -- gate verdicts ------------------------------------------------------------


def _cap(bench, value, fp, rev="r01", better="higher", rel_tol=0.1):
    return wrap({"metric": "m", "value": value},
                bench=bench, rev=rev,
                metrics={"m": metric(value, "u", better, rel_tol)},
                fingerprint=fp)


class TestGateVerdicts:
    def test_missing_baseline_records(self, tmp_path):
        ledger = CaptureLedger(str(tmp_path))
        r = gate_capture(_cap("newfam", 1.0, FP_CPU), ledger)
        assert r.status == RECORD and r.ok
        assert "no baseline" in r.reason

    def test_fingerprint_mismatch_records_not_fails(self, tmp_path):
        ledger = CaptureLedger(str(tmp_path))
        ledger.write("FAM_x_r01.json", {"metric": "m", "value": 100.0},
                     bench="fam", rev="r01",
                     metrics={"m": metric(100.0, rel_tol=0.1)},
                     fingerprint=FP_CPU)
        # a (much worse) first TPU capture must RECORD, never fight the
        # CPU baseline
        r = gate_capture(_cap("fam", 1.0, FP_TPU), ledger)
        assert r.status == RECORD and r.ok
        assert "fingerprint mismatch" in r.reason

    def test_synthetic_regression_fails_and_names_the_metric(self, tmp_path):
        ledger = CaptureLedger(str(tmp_path))
        ledger.write("FAM_x_r01.json", {"metric": "m", "value": 100.0},
                     bench="fam", rev="r01",
                     metrics={"tokens_per_sec": metric(100.0, "tok/s",
                                                       rel_tol=0.1)},
                     fingerprint=FP_CPU)
        fresh = wrap({"metric": "m", "value": 50.0}, bench="fam", rev="r02",
                     metrics={"tokens_per_sec": metric(50.0, "tok/s",
                                                       rel_tol=0.1)},
                     fingerprint=FP_CPU)
        r = gate_capture(fresh, ledger)
        assert r.status == FAIL and not r.ok
        assert any("tokens_per_sec" in f for f in r.failures)
        # the failure string carries both values + the band, not just
        # "regressed"
        assert any("100" in f and "50" in f for f in r.failures)

    def test_within_band_passes_against_newest_same_fingerprint(
            self, tmp_path):
        ledger = CaptureLedger(str(tmp_path))
        ledger.write("FAM_x_r01.json", {"metric": "m", "value": 100.0},
                     bench="fam", rev="r01",
                     metrics={"m": metric(100.0, rel_tol=0.1)},
                     fingerprint=FP_CPU)
        r = gate_capture(_cap("fam", 95.0, FP_CPU), ledger)
        assert r.status == PASS and r.ok
        assert r.baseline_path and r.baseline_path.endswith("FAM_x_r01.json")

    def test_self_gate_is_always_pass(self):
        doc = _cap("fam", 42.0, FP_CPU)
        assert evaluate_capture(doc, doc).status == PASS


# -- envelope / ledger round-trip --------------------------------------------


class TestLedgerRoundTrip:
    def test_save_capture_roundtrip(self, tmp_path):
        path = str(tmp_path / "SMOKE_test_r03.json")
        payload = {"metric": "smoke_tok_s", "value": 12.5, "unit": "tok/s",
                   "extra": {"nested": True}}
        save_capture(path, dict(payload), fingerprint=FP_CPU)
        doc = load_capture(path)
        # additive: the original payload keys survive at top level
        assert payload_of(doc) == payload
        env = envelope_of(doc)
        assert env["schema"] == 1
        assert env["bench"] == "SMOKE_test" and env["rev"] == "r03"
        assert env["fingerprint"] == FP_CPU
        assert env["metrics"]["smoke_tok_s"]["value"] == 12.5
        assert validate_envelope(doc) == []

    def test_migrated_legacy_capture_roundtrip(self, tmp_path):
        from ray_tpu.obs.perfwatch.migrate import migrate_file

        legacy = {"metric": "legacy_tok_s", "value": 77.0, "unit": "tok/s",
                  "coverage_pct": 91.5}
        path = str(tmp_path / "LEGACY_fam_r09.json")
        with open(path, "w") as f:
            json.dump(legacy, f)
        assert migrate_file(path) is not None
        doc = load_capture(path)
        assert validate_envelope(doc) == []
        assert payload_of(doc) == legacy
        env = envelope_of(doc)
        assert env["bench"] == "LEGACY_fam" and env["rev"] == "r09"
        m = env["metrics"]
        assert m["legacy_tok_s"]["value"] == 77.0
        assert m["coverage_pct"]["value"] == 91.5
        # migrating twice is a no-op (the envelope is already there)
        assert migrate_file(path) is None

    def test_validate_envelope_catches_corruption(self):
        doc = _cap("fam", 1.0, FP_CPU)
        doc["perfwatch"]["metrics"]["bad"] = {
            "value": float("nan"), "better": "sideways", "rel_tol": -1}
        problems = validate_envelope(doc)
        assert any("non-numeric" in p for p in problems)
        assert any("sideways" in p for p in problems)
        assert any("rel_tol" in p for p in problems)

    def test_repo_ledger_passes_run_check(self):
        # THE tier-1 gate: every checked-in capture enveloped,
        # schema-valid, self-consistent under the band math
        problems = run_check(os.path.join(REPO, "benchmarks"))
        assert problems == [], "\n".join(problems)
