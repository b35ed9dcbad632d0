"""What the readers of a Kimi-Linear cell have in common. Each returns None
where there is nothing to read (a run with no trace, a program with no
`kda.*` or `mla.*` scope: the parent of the PR that added the model), so the
line leaves the metric out.

Device time is read from the step's table (readers_step.py: every operation
of the traced window booked to the INNERMOST listed scope of its path). The
KDA rule's kernels stand under `kda.scan`; the MLA layer's flash kernels
take the name of their scope (`kernel:mla.attend.N`)."""

from __future__ import annotations

from typing import Optional

from chipbench import costs_kimi_linear, readers, readers_step
from chipbench.readers_laguna import kernel_seconds
# a cost against the time spent, and a step's [batch a chip, sequence]: that cell's and this one's
from chipbench.readers_solar_open2 import _least_pct, _per_chip
from chipbench.readers_zaya import held_pairs


def is_kimi_linear(run: dict) -> bool:
    return "kda_layers" in ((run.get("shape") or {}).get("linear_attn_config") or {})


def scan_roofline(run: dict) -> Optional[float]:
    """Everything under `kda.scan` (every KDA layer, forward and backward,
    whatever computes it) against the larger of operations / peak FLOP/s and
    bytes / peak bytes/s of the position-by-position rule at all the heads."""
    if not is_kimi_linear(run):
        return None
    table = readers_step.step_table(run)
    row = None if table is None else table["scopes"].get("kda.scan")
    if row is None or not row["seconds"]:
        return None
    return _least_pct(run, costs_kimi_linear.scan_cost(run["shape"], *_per_chip(run)),
                      row["seconds"])


def flash_roofline(run: dict) -> Optional[float]:
    """The `mla.attend.N` kernels (the MLA layers', forward and backward)
    against attention's cost at keys of d_n + d_r and values of d_v."""
    if not is_kimi_linear(run):
        return None
    spent = kernel_seconds(run, "mla.attend")
    if not spent:
        return None
    return _least_pct(run, costs_kimi_linear.flash_cost(run["shape"], *_per_chip(run)), spent)


def expert_matmul_roofline_held(run: dict) -> Optional[float]:
    """All grouped-matmul kernels against NINE matmuls an expert layer over
    the pairs actually routed to the held experts."""
    if not is_kimi_linear(run):
        return None
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    if not spent or pairs is None:
        return None
    layers = costs_kimi_linear.expert_layers(run["shape"])
    # the held rows of a step, spread over its expert layers: operations are linear in the
    # rows and every layer moves its own weights, so the mean layer times `layers`
    cost = costs_kimi_linear.grouped_matmul_cost(run["shape"], pairs["held"] / run["chips"] / layers)
    return _least_pct(run, cost, spent, times=layers)


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None or not is_kimi_linear(run):
        return None
    per_token = costs_kimi_linear.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
