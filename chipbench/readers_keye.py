"""What the readers of the Keye share's cell have in common. Each returns
None where there is nothing to read (a run with no trace, a program with
no `dsa.*` scope or no `pairs_elsewhere` statistic: the parent of the PR
that added them), so the line leaves the metric out.

Device time is read from the step's table (readers_step.py: every
operation of the traced window booked to the INNERMOST listed scope of
its path; chipbench/step_scopes/keye.json brings the families `dsa`,
`dsa_index` and `dsa_select`). A flash kernel takes the name of the
scope it is called in (`kernel:dsa.attend.N`) and is booked there."""

from __future__ import annotations

from typing import Optional

from chipbench import costs, costs_keye, readers, readers_step
from chipbench.readers_zaya import held_pairs

FAMILIES = ("dsa", "dsa_index", "dsa_select")


def is_keye(run: dict) -> bool:
    return "sa_config" in (run.get("shape") or {})


def families_pct(run: dict, families: tuple) -> Optional[float]:
    """Device time of the families together, % of the traced window's
    busy time; None (not 0.0) where nothing ran under any `dsa*` family:
    a program that lacks the scopes has nothing to read."""
    table = readers_step.step_table(run)
    if table is None:
        return None
    seconds = readers_step.family_seconds(table)
    if not any(seconds.get(f) for f in FAMILIES):
        return None
    return 100.0 * sum(seconds.get(f, 0.0) for f in families) / table["busy_s"]


def flash_roofline_selected(run: dict) -> Optional[float]:
    """The kernels under `dsa.attend` (every layer, forward and backward)
    against the larger of operations / peak FLOP/s and bytes / peak
    bytes/s of the SELECTED pairs."""
    table = readers_step.step_table(run)
    row = None if table is None else table["scopes"].get("dsa.attend")
    if row is None or not is_keye(run):
        return None
    spent = sum(s for op, s in row["ops"].items() if op.startswith(readers_step.KERNEL))
    if not spent:
        return None
    shape = run["shape"]
    c = costs_keye.flash_cost(shape, shape["train"]["global_batch"] / run["chips"],
                              run["traffic"]["seq_len"])
    n = shape["num_hidden_layers"] * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def expert_matmul_roofline_held(run: dict) -> Optional[float]:
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    if not spent or pairs is None or not is_keye(run):
        return None
    shape = run["shape"]
    layers = shape["num_hidden_layers"]
    # the held rows of a step, spread over its layers: operations are linear in the
    # rows and every layer moves its own weights, so the mean layer times `layers`
    c = costs_keye.grouped_matmul_cost(shape, pairs["held"] / run["chips"] / layers)
    n = layers * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None or not is_keye(run):
        return None
    per_token = costs_keye.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
