"""What the readers of a ZAYA1 share's cell have in common. Each returns
None where there is nothing to read (a run with no trace, a program
with no `cca.*` scope or no `pairs_elsewhere` statistic: the parent of
the PR that added them), so the line leaves the metric out.

CCA's device time is read as the expert layer's is (readers_moe.py):
ops under the program's `cca.*` named scopes, through the compiled
step's HLO text (run["cca_scopes"], set by
runners/train_reference_from_config.py), and the flash kernels, which
carry no scope in a trace and are classed by name."""

from __future__ import annotations

import statistics
from typing import Optional

from chipbench import costs, costs_zaya, hlo_scopes, readers
from chipbench.readers_moe import share_pct  # noqa: F401 - the CCA readers' too

FLASH_CLASSES = ("flash_fwd", "flash_bwd", "flash")


def cca_seconds(run: dict) -> Optional[dict]:
    """{"scoped": {scope: seconds}, "kernels": seconds} of the traced
    window; None without a trace or where no op ran under a `cca.*` scope."""
    if not run.get("trace") or not run.get("cca_scopes"):
        return None
    scoped = hlo_scopes.seconds_by_scope(run["trace"], run["win"], run["cca_scopes"])
    if not scoped:
        return None
    kernels = sum(readers.class_seconds(run, "ops", c) or 0.0 for c in FLASH_CLASSES)
    return {"scoped": scoped, "kernels": kernels}


def held_pairs(run: dict) -> Optional[dict]:
    """{"held", "all"}: (token, expert) pairs of a step summed over its
    layers, those routed to held experts and all of them; medians of the
    traced steps. None where the steps report no `pairs_elsewhere`."""
    steps = [m["router"] for m in run.get("traced_window_steps", ())
             if m.get("router") and m["router"].get("pairs_elsewhere") is not None]
    if not steps:
        return None
    every = statistics.median(sum(r["pairs"]) for r in steps)
    return {"all": every,
            "held": statistics.median(sum(r["pairs"]) - sum(r["pairs_elsewhere"]) for r in steps)}


def expert_matmul_roofline_held(run: dict) -> Optional[float]:
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    if not spent or pairs is None:
        return None
    shape = run["shape"]
    layers = shape["num_hidden_layers"]
    # the held rows of a step, spread over its layers: operations are linear in the
    # rows and every layer moves its own weights, so the mean layer times `layers`
    c = costs_zaya.grouped_matmul_cost(shape, pairs["held"] / run["chips"] / layers)
    n = layers * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None:
        return None
    per_token = costs_zaya.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
