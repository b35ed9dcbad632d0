"""Live metrics-registry lint (the original ``check_metrics``, now under
the shared analysis umbrella).

Unlike the AST passes this one runs against the LIVE registry: it
imports every instrumented module, forces lazily-registered metrics to
register, then walks ``util/metrics``'s registry and fails on missing
descriptions, names outside the ``ray_tpu_``/``llm_`` conventions,
type conflicts (including histogram ``_sum``/``_count``/``_bucket``
exposition-series collisions), and telemetry-plane gauges with no
declared aggregation kind.

CLI shim: ``python scripts/check_metrics.py`` (exit 1 on problems).
"""

from __future__ import annotations

import re

# every module that registers metrics, plus the hook that forces lazy
# singletons to register
INSTRUMENTED = [
    ("ray_tpu.obs.slo", "register_all"),
    ("ray_tpu.obs.telemetry", "register_metrics"),
    ("ray_tpu.obs.recorder", "register_metrics"),
    ("ray_tpu.llm.spec.stats", "_spec_metrics"),
    ("ray_tpu.llm.admission", "register_metrics"),
    ("ray_tpu.llm.engine", "register_metrics"),
    ("ray_tpu.cluster.node_daemon", "register_metrics"),
    ("ray_tpu.cluster.gcs_service", "register_metrics"),
    ("ray_tpu.serve.controller", "register_metrics"),
    ("ray_tpu.train.elastic", "register_metrics"),
    ("ray_tpu.fabric.metrics", "register_metrics"),
    ("ray_tpu.llm.kvtier.metrics", "register_metrics"),
    ("ray_tpu.llm.kvfetch.metrics", "register_metrics"),
    ("ray_tpu.rl.post_train.metrics", "register_metrics"),
    ("ray_tpu.autoscale.metrics", "register_metrics"),
    ("ray_tpu.fleet.metrics", "register_metrics"),
    ("ray_tpu.cluster.lockstats", "register_metrics"),
]

_NAME_RE = re.compile(r"^(ray_tpu|llm)_[a-z0-9][a-z0-9_]*$")
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def register_instrumented_metrics() -> list[str]:
    """Import instrumented modules + fire their registration hooks;
    returns import problems (a module that stops importing is itself a
    regression this gate should catch)."""
    import importlib

    problems = []
    for mod_name, hook in INSTRUMENTED:
        try:
            getattr(importlib.import_module(mod_name), hook)()
        except Exception as e:  # noqa: BLE001
            problems.append(f"{mod_name}: import/registration failed: {e!r}")
    return problems


def check_registry() -> list[str]:
    """Walk the live registry; returns a list of problem strings."""
    from ray_tpu.util.metrics import Histogram, registry_snapshot

    problems = []
    metrics = registry_snapshot()
    seen: dict[str, str] = {}
    hist_names = {m.name for m in metrics if isinstance(m, Histogram)}
    for m in metrics:
        if not m.description.strip():
            problems.append(f"{m.name}: missing description")
        if not _NAME_RE.match(m.name):
            problems.append(
                f"{m.name}: name outside the ray_tpu_/llm_ convention "
                "(lowercase, [a-z0-9_], subsystem-prefixed)"
            )
        prior = seen.get(m.name)
        if prior is not None and prior != m.TYPE:
            problems.append(
                f"{m.name}: registered as both {prior} and {m.TYPE}"
            )
        seen[m.name] = m.TYPE
        # a non-histogram named <hist>_sum/_count/_bucket collides with
        # the exposition series histogram <hist> generates
        for suffix in _HIST_SUFFIXES:
            if m.name.endswith(suffix) and m.name[: -len(suffix)] in hist_names:
                problems.append(
                    f"{m.name}: collides with histogram "
                    f"{m.name[:-len(suffix)]!r}'s {suffix} series"
                )
    return problems


def check_aggregations() -> list[str]:
    """Telemetry-plane lint: every gauge/counter under the aggregated
    name prefixes must resolve to a valid aggregation kind. Counters
    default to sum; gauges must be explicitly declared (sum vs max is a
    semantic choice the metric's owner makes — see obs/telemetry.py)."""
    from ray_tpu.obs import telemetry
    from ray_tpu.util.metrics import registry_snapshot

    problems = []
    for m in registry_snapshot():
        if m.TYPE == "histogram":
            continue  # bucket merge is the only sane histogram rollup
        if not m.name.startswith(telemetry.AGGREGATED_PREFIXES):
            continue
        kind = telemetry.aggregation_kind(m.name, m.TYPE)
        if kind is None:
            problems.append(
                f"{m.name}: telemetry-plane {m.TYPE} with no declared "
                "aggregation kind (declare sum/max via "
                "obs.telemetry.declare_aggregation or the cluster_* helpers)"
            )
        elif kind not in telemetry.VALID_AGGREGATIONS:
            problems.append(
                f"{m.name}: invalid aggregation kind {kind!r}"
            )
    return problems


def run_check() -> list[str]:
    return (register_instrumented_metrics() + check_registry()
            + check_aggregations())


def main() -> int:
    problems = run_check()
    if problems:
        print(f"check_metrics: {len(problems)} problem(s):")
        for p in problems:
            print(f"  - {p}")
        return 1
    from ray_tpu.util.metrics import registry_snapshot

    print(f"check_metrics: ok ({len(registry_snapshot())} metrics clean)")
    return 0
