"""What the readers of a Solar-Open2 cell have in common. Each returns
None where there is nothing to read (a run with no trace, a program with
no `kda.*` scope: the parent of the PR that added them), so the line
leaves the metric out.

Device time is read from the step's table (readers_step.py: every
operation of the traced window booked to the INNERMOST listed scope of
its path; chipbench/step_scopes/solar_open2.json brings the families
`kda_proj`, `kda_scan` and `kda_glue`). The rule's loop over the chunks
stands under `kda.scan` with everything in its body; the GQA layer's
flash kernels take the name of their scope (`kernel:attn.attend.N`)."""

from __future__ import annotations

from typing import Optional

from chipbench import costs, costs_solar_open2, readers, readers_step
from chipbench.readers_laguna import kernel_seconds
from chipbench.readers_zaya import held_pairs

FAMILIES = ("kda_proj", "kda_scan", "kda_glue")


def is_solar_open2(run: dict) -> bool:
    return "gqa_layers" in (run.get("shape") or {})


def families_pct(run: dict, families: tuple = FAMILIES) -> Optional[float]:
    """Device time of the families together, % of the traced window's
    busy time; None (not 0.0) where nothing ran under any `kda*` family."""
    table = readers_step.step_table(run)
    if table is None:
        return None
    seconds = readers_step.family_seconds(table)
    if not any(seconds.get(f) for f in FAMILIES):
        return None
    return 100.0 * sum(seconds.get(f, 0.0) for f in families) / table["busy_s"]


def _least_pct(run: dict, cost: dict, spent: float, times: float = 1.0) -> float:
    n = times * run["traced_steps"]
    least, _ = costs.roofline_seconds(n * (cost["fwd_flops"] + cost["bwd_flops"]),
                                      n * (cost["fwd_bytes"] + cost["bwd_bytes"]), run["peaks"])
    return 100.0 * least / spent


def _per_chip(run: dict) -> tuple:
    return run["shape"]["train"]["global_batch"] / run["chips"], run["traffic"]["seq_len"]


def scan_roofline(run: dict) -> Optional[float]:
    """Everything under `kda.scan` (every KDA layer, forward and backward,
    whatever computes it) against the larger of operations / peak FLOP/s
    and bytes / peak bytes/s of the position-by-position rule."""
    table = readers_step.step_table(run)
    row = None if table is None else table["scopes"].get("kda.scan")
    if row is None or not row["seconds"] or not is_solar_open2(run):
        return None
    return _least_pct(run, costs_solar_open2.scan_cost(run["shape"], *_per_chip(run)),
                      row["seconds"])


def flash_roofline(run: dict) -> Optional[float]:
    """The `attn.attend.N` kernels (the GQA layer's, forward and backward)
    against the flash cost at the held heads."""
    if not is_solar_open2(run):
        return None
    spent = kernel_seconds(run, "attn.attend")
    if not spent:
        return None
    return _least_pct(run, costs_solar_open2.flash_cost(run["shape"], *_per_chip(run)), spent)


def expert_matmul_roofline_held(run: dict) -> Optional[float]:
    """All grouped-matmul kernels against NINE matmuls a layer over the
    pairs actually routed to the held experts."""
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    if not spent or pairs is None or not is_solar_open2(run):
        return None
    layers = run["shape"]["num_hidden_layers"]
    # the held rows of a step, spread over its layers: operations are linear in the
    # rows and every layer moves its own weights, so the mean layer times `layers`
    cost = costs_solar_open2.grouped_matmul_cost(run["shape"], pairs["held"] / run["chips"] / layers)
    return _least_pct(run, cost, spent, times=layers)


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None or not is_solar_open2(run):
        return None
    per_token = costs_solar_open2.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
