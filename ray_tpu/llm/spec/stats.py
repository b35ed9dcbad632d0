"""Acceptance-rate accounting + observability export.

Two surfaces:

 * SpecStats — host counters the engine folds into ``stats()``;
 * Prometheus — counters/gauges on the dashboard /metrics route
   (util/metrics.py process-wide registry).

A verify round's time is the engine's layer span ``engine.step`` with
its children (``engine.decode_dispatch`` = reserve + verify dispatch,
``engine.sync`` = accept + host sync).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class SpecStats:
    """Host-side running totals for one engine."""

    steps: int = 0       # verification passes dispatched
    rows: int = 0        # sequence-rows verified (sum of batch sizes)
    drafted: int = 0     # draft tokens proposed
    accepted: int = 0    # draft tokens accepted
    emitted: int = 0     # tokens actually kept (accepted + bonus, post-stop)

    @property
    def acceptance_rate(self) -> float:
        """Accepted / drafted — drafter quality (1.0 = every guess right)."""
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def mean_accepted_len(self) -> float:
        """Tokens emitted per row per verify pass (incl. the bonus token):
        the speedup lever — n bandwidth-bound decode steps collapse into
        one verify pass when this is n."""
        return self.emitted / self.rows if self.rows else 0.0

    def to_dict(self) -> dict:
        return {
            "steps": self.steps,
            "rows": self.rows,
            "drafted_tokens": self.drafted,
            "accepted_tokens": self.accepted,
            "emitted_tokens": self.emitted,
            "acceptance_rate": round(self.acceptance_rate, 4),
            "mean_accepted_len": round(self.mean_accepted_len, 4),
        }


_metrics = None


def _spec_metrics():
    """Lazy singletons (same-name re-registration shares storage, but the
    first construction still takes the registry lock — keep it off the
    per-chunk path)."""
    global _metrics
    if _metrics is None:
        from ray_tpu.obs.telemetry import AGG_MAX, declare_aggregation
        from ray_tpu.util.metrics import Counter, Gauge

        # cluster-telemetry aggregation: the fleet-level acceptance rate
        # derives from the drafted/accepted counter SUMS; the gauges are
        # per-engine running rates, where max is the honest rollup
        # (averaging rates across unevenly-loaded engines lies)
        declare_aggregation("llm_spec_acceptance_rate", AGG_MAX)
        declare_aggregation("llm_spec_mean_accepted_len", AGG_MAX)
        _metrics = {
            "drafted": Counter(
                "llm_spec_drafted_tokens_total",
                description="speculative decoding: draft tokens proposed",
            ),
            "accepted": Counter(
                "llm_spec_accepted_tokens_total",
                description="speculative decoding: draft tokens accepted",
            ),
            "emitted": Counter(
                "llm_spec_emitted_tokens_total",
                description="speculative decoding: tokens emitted by verify "
                "passes (accepted + bonus, after stop conditions)",
            ),
            "acceptance_rate": Gauge(
                "llm_spec_acceptance_rate",
                description="speculative decoding: running accepted/drafted",
            ),
            "mean_accepted_len": Gauge(
                "llm_spec_mean_accepted_len",
                description="speculative decoding: running emitted tokens per "
                "verified row (includes the bonus token)",
            ),
        }
    return _metrics


def export_spec_stats(stats: SpecStats, drafted: int, accepted: int,
                      emitted: int) -> None:
    """Publish one verify pass's deltas + the running rates. Observability
    must not break decode: failures are swallowed."""
    try:
        m = _spec_metrics()
        if drafted:
            m["drafted"].inc(drafted)
        if accepted:
            m["accepted"].inc(accepted)
        if emitted:
            m["emitted"].inc(emitted)
        m["acceptance_rate"].set(stats.acceptance_rate)
        m["mean_accepted_len"].set(stats.mean_accepted_len)
    except Exception:  # noqa: BLE001 — observability must not break decode
        pass
