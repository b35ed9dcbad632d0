"""What the readers of an Olmo-Hybrid cell have in common. Each returns
None where there is nothing to read (a run with no trace, a program with
no `gdn.*` scope: the parent of the PR that added them), so the line
leaves the metric out.

Device time is read from the step's table (readers_step.py: every
operation of the traced window booked to the INNERMOST listed scope of
its path; chipbench/step_scopes/olmo_hybrid.json brings the families
`gdn_proj`, `gdn_scan` and `gdn_glue`). The recurrence's loops over the
chunks stand under `gdn.scan` with everything in their bodies; the full
layer's flash kernels take the name of their scope (`kernel:attn.attend.N`)."""

from __future__ import annotations

from typing import Optional

from chipbench import costs, costs_olmo_hybrid, readers_step
from chipbench.readers_laguna import kernel_seconds

FAMILIES = ("gdn_proj", "gdn_scan", "gdn_glue")


def is_olmo_hybrid(run: dict) -> bool:
    return "linear_key_head_dim" in (run.get("shape") or {})


def families_pct(run: dict, families: tuple = FAMILIES) -> Optional[float]:
    """Device time of the families together, % of the traced window's
    busy time; None (not 0.0) where nothing ran under any `gdn*` family."""
    table = readers_step.step_table(run)
    if table is None:
        return None
    seconds = readers_step.family_seconds(table)
    if not any(seconds.get(f) for f in FAMILIES):
        return None
    return 100.0 * sum(seconds.get(f, 0.0) for f in families) / table["busy_s"]


def _least_pct(run: dict, cost: dict, spent: float) -> float:
    n = run["traced_steps"]
    least, _ = costs.roofline_seconds(n * (cost["fwd_flops"] + cost["bwd_flops"]),
                                      n * (cost["fwd_bytes"] + cost["bwd_bytes"]), run["peaks"])
    return 100.0 * least / spent


def _per_chip(run: dict) -> tuple:
    return run["shape"]["train"]["global_batch"] / run["chips"], run["traffic"]["seq_len"]


def scan_roofline(run: dict) -> Optional[float]:
    """Everything under `gdn.scan` (every linear layer, forward and
    backward, whatever computes it) against the larger of operations /
    peak FLOP/s and bytes / peak bytes/s of the position-by-position rule."""
    table = readers_step.step_table(run)
    row = None if table is None else table["scopes"].get("gdn.scan")
    if row is None or not row["seconds"] or not is_olmo_hybrid(run):
        return None
    return _least_pct(run, costs_olmo_hybrid.scan_cost(run["shape"], *_per_chip(run)),
                      row["seconds"])


def flash_roofline(run: dict) -> Optional[float]:
    """The `attn.attend.N` kernels (the full layers', forward and
    backward) against the flash cost at their own heads."""
    if not is_olmo_hybrid(run):
        return None
    spent = kernel_seconds(run, "attn.attend")
    if not spent:
        return None
    return _least_pct(run, costs_olmo_hybrid.flash_cost(run["shape"], *_per_chip(run)), spent)


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    if not rate or not is_olmo_hybrid(run):
        return None
    per_token = costs_olmo_hybrid.train_flops_per_token(run["shape"], run["traffic"]["seq_len"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
