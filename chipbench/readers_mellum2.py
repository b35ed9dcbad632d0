"""What the readers of a Mellum2 share's cell have in common. Each returns
None where there is nothing to read (a run with no trace, a configuration
that is not `model_type` mellum, a program with no `swa.norm` /
`attn.norm` scope or no `pairs_elsewhere` statistic: the parent of the PR
that added them), so the line leaves the metric out.

Device time is read from the step's table (readers_step.py: every
operation of the traced window booked to the INNERMOST listed scope of
its path; chipbench/step_scopes/mellum2.json brings the family `qk_norm`:
`swa.norm` and `attn.norm`). A flash kernel takes the name of the
scope it is called in (`kernel:swa.attend.N`, `kernel:attn.attend.N`) and
is booked there, so the window kernels and the full layer's are told
apart by their scope, forward, dq and dk/dv alike. Laguna's readers
(readers_laguna.py) spell out its head counts by layer and its gate:
these stand beside them and share what is generic (`kernel_seconds`,
`held_pairs`)."""

from __future__ import annotations

from typing import Optional

from chipbench import costs, costs_mellum2, readers, readers_step
from chipbench.readers_laguna import kernel_seconds
from chipbench.readers_zaya import held_pairs

KINDS = {"swa.attend": costs_mellum2.SLIDING, "attn.attend": costs_mellum2.FULL}


def is_mellum2(run: dict) -> bool:
    return (run.get("shape") or {}).get("model_type") == "mellum"


def flash_roofline(run: dict, scope: str) -> Optional[float]:
    """The kernels under `scope` (all the layers of its kind: forward, and
    the backward as one fused kernel or as dq and dk/dv apart) against the
    larger of operations / peak FLOP/s and bytes / peak bytes/s of the
    pairs their mask leaves visible."""
    if not is_mellum2(run):
        return None
    spent = kernel_seconds(run, scope)
    if not spent:
        return None
    shape = run["shape"]
    c = costs_mellum2.flash_cost(shape, KINDS[scope], shape["train"]["global_batch"] / run["chips"],
                                 run["traffic"]["seq_len"])
    n = run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def expert_matmul_roofline(run: dict) -> Optional[float]:
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    if not spent or pairs is None or not is_mellum2(run):
        return None
    shape = run["shape"]
    n_layers = shape["num_hidden_layers"]
    # the held rows of a step, spread over its layers: operations are linear in the
    # rows and every layer moves its own weights, so the mean layer times their number
    c = costs_mellum2.grouped_matmul_cost(shape, pairs["held"] / run["chips"] / n_layers)
    n = n_layers * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None or not is_mellum2(run):
        return None
    per_token = costs_mellum2.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])


def qk_norm_pct(run: dict) -> Optional[float]:
    """Device time booked to the RMSNorm a head on q and k (the family
    `qk_norm`: `swa.norm`, `attn.norm`), % of the traced window's busy
    time; 0.0 where the record is there and XLA fused every one of the
    norm's operations into a neighbour's pass (the rotary's, a
    projection's), which the table books to that neighbour."""
    return readers_step.family_pct(run, "qk_norm") if is_mellum2(run) else None
