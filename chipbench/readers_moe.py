"""What the expert layer's per-layer readers share. Each returns None
where there is nothing to read (a run with no trace, a step with no
expert scopes or statistics), so the line leaves the metric out.

Device time of the expert layer is read two ways that do not overlap:
ops under the program's `moe.*` named scopes, attributed through the
compiled step's HLO text (run["scopes"], chipbench/hlo_scopes.py), and
the grouped-matmul kernels, which XLA names itself and which carry no
scope (class `expert_matmul`, with the small `expert_matmul_schedule`
kernels that prepare their tiles; chipbench/trace_names)."""

from __future__ import annotations

import statistics
from typing import Optional

from chipbench import costs, costs_moe, hlo_scopes, readers

KERNEL_CLASSES = ("expert_matmul", "expert_matmul_schedule")


def moe_seconds(run: dict) -> Optional[dict]:
    """{"scoped": {scope: seconds}, "kernels": seconds}: the expert
    layer's device time in the traced window; None without a trace or
    where no op ran under a `moe.*` scope."""
    if not run.get("trace") or not run.get("scopes"):
        return None
    scoped = hlo_scopes.seconds_by_scope(run["trace"], run["win"], run["scopes"])
    if not scoped:
        return None
    kernels = sum(readers.class_seconds(run, "ops", c) or 0.0 for c in KERNEL_CLASSES)
    return {"scoped": scoped, "kernels": kernels}


def share_pct(run: dict, seconds: float) -> float:
    """Of the device time in ops inside the traced window (%)."""
    return 100.0 * seconds / run["busy"]["busy_s"]


def expert_matmul_roofline(run: dict) -> Optional[float]:
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    if not spent:
        return None
    shape = run["shape"]
    tokens = run["tokens_per_step"] // run["chips"]
    c = costs_moe.grouped_matmul_cost(shape, tokens)
    n = shape["num_hidden_layers"] * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def expert_imbalance(run: dict) -> Optional[float]:
    """Median over the traced steps of the largest (over layers) ratio
    of an expert's tokens to the mean."""
    worst = [max(m["router"]["imbalance"]) for m in run.get("traced_window_steps", ())
             if m.get("router")]
    return statistics.median(worst) if worst else None
