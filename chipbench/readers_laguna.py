"""What the readers of a Laguna share's cell have in common. Each returns
None where there is nothing to read (a run with no trace, a program
with no `swa.*` scope, no `*.gate` scope or no `pairs_elsewhere`
statistic: the parent of the PR that added them), so the line leaves
the metric out.

Device time is read from the step's table (readers_step.py: every
operation of the traced window booked to the INNERMOST listed scope of
its path; chipbench/step_scopes/laguna.json brings the families `swa`
and `gate`). A flash kernel takes the name of the scope it is called in
(`kernel:swa.attend.N`, `kernel:attn.attend.N`) and is booked there, so
the window kernels and the full ones are told apart by their scope."""

from __future__ import annotations

from typing import Optional

from chipbench import costs, costs_laguna, readers, readers_step
from chipbench.readers_zaya import held_pairs

KINDS = {"swa.attend": costs_laguna.SLIDING, "attn.attend": costs_laguna.FULL}


def is_laguna(run: dict) -> bool:
    return "sliding_window" in (run.get("shape") or {})


def kernel_seconds(run: dict, scope: str) -> Optional[float]:
    """Seconds of the Pallas kernels booked to `scope` in the traced window."""
    table = readers_step.step_table(run)
    row = None if table is None else table["scopes"].get(scope)
    if row is None:
        return None
    return sum(s for op, s in row["ops"].items() if op.startswith(readers_step.KERNEL)) or None


def flash_roofline(run: dict, scope: str) -> Optional[float]:
    """The kernels under `scope` (all the layers of its kind, forward and
    backward) against the larger of operations / peak FLOP/s and bytes /
    peak bytes/s of the pairs their mask leaves visible."""
    if not is_laguna(run):
        return None
    spent = kernel_seconds(run, scope)
    if not spent:
        return None
    shape = run["shape"]
    c = costs_laguna.flash_cost(shape, KINDS[scope], shape["train"]["global_batch"] / run["chips"],
                                run["traffic"]["seq_len"])
    n = run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def expert_matmul_roofline_held(run: dict) -> Optional[float]:
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    if not spent or pairs is None or not is_laguna(run):
        return None
    shape = run["shape"]
    n_blocks = sum(1 for _, _, dense in costs_laguna.layers(shape) if not dense)
    # the held rows of a step, spread over its blocks: operations are linear in the
    # rows and every block moves its own weights, so the mean block times their number
    c = costs_laguna.grouped_matmul_cost(shape, pairs["held"] / run["chips"] / n_blocks)
    n = n_blocks * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None or not is_laguna(run):
        return None
    per_token = costs_laguna.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])


def family_pct(run: dict, family: str) -> Optional[float]:
    """readers_step.family_pct, and None (not 0.0) where nothing ran under
    the family: a program that lacks the scopes has nothing to read."""
    got = readers_step.family_pct(run, family)
    return got or None
