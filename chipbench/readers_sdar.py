"""What the readers of an SDAR share's cell have in common. Each returns
None where there is nothing to read (a run with no trace, a configuration
that is not `model_type` sdar_moe, a program with no `diff.*` scope or no `diffusion` record: the parent of the PR that added
them), so the line leaves the metric out.

Device time is read from the step's table (readers_step.py: every
operation of the traced window booked to the INNERMOST listed scope of
its path; chipbench/step_scopes/sdar.json brings the families `diff` and
`blockdiff_merge`). The masked flash kernels are called inside
`attn.attend` under an unlisted scope of their own, which names them in a
trace (`kernel:flash.blockdiff.N`, forward and fused backward alike) and
leaves them booked to `attn.attend`, where they are the only kernels and,
the merge having a listed scope of its own, next to the only operations. `run["diffusion"]` is the step's own report of
its mask (runners/train_reference_sdar.py keeps the first step's
`diff_*` statistics there)."""

from __future__ import annotations

from typing import Optional

from chipbench import costs, costs_sdar, readers, readers_step
from chipbench.readers_laguna import kernel_seconds
from chipbench.readers_zaya import held_pairs

KERNELS = "attn.attend"  # the scope the masked kernels are booked to


def is_sdar(run: dict) -> bool:
    return (run.get("shape") or {}).get("model_type") == "sdar_moe"


def flash_roofline(run: dict) -> Optional[float]:
    """The masked kernels (all layers, forward and backward) against the
    larger of operations / peak FLOP/s and bytes / peak bytes/s of the
    L (L + beta) pairs a head the mask leaves VISIBLE."""
    if not is_sdar(run):
        return None
    spent = kernel_seconds(run, KERNELS)
    if not spent:
        return None
    shape = run["shape"]
    c = costs_sdar.flash_cost(shape, shape["train"]["global_batch"] / run["chips"],
                              run["traffic"]["seq_len"])
    n = run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def expert_matmul_roofline(run: dict) -> Optional[float]:
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    if not spent or pairs is None or not is_sdar(run):
        return None
    shape = run["shape"]
    n_layers = shape["num_hidden_layers"]
    # the held rows of a step (both copies'), spread over its layers: operations are linear
    # in the rows and every layer moves its own weights, so the mean layer times their number
    c = costs_sdar.grouped_matmul_cost(shape, pairs["held"] / run["chips"] / n_layers)
    n = n_layers * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None or not is_sdar(run):
        return None
    per_token = costs_sdar.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])


def diff_share_pct(run: dict) -> Optional[float]:
    """Device time booked to the objective's own scopes (`diff.corrupt`:
    the levels, the draws, the second copy; `diff.loss`: the weights, the
    head's matmuls and the weighted cross-entropy over the L noised rows,
    forward and backward), % of the traced window's busy time."""
    return readers_step.family_pct(run, "diff") if is_sdar(run) else None


def blockdiff_merge_pct(run: dict) -> Optional[float]:
    """Device time of what the masked attention does OUTSIDE its kernels
    (`flash.blockdiff_merge`: each noised block's own [beta, beta] product
    and the log-sum-exp merge with the kernels' result, forward and
    backward), % of the traced window's busy time: the method's own cost
    beside the second copy's rows."""
    return readers_step.family_pct(run, "blockdiff_merge") if is_sdar(run) else None


def qk_norm_pct(run: dict) -> Optional[float]:
    """Device time booked to the RMSNorm a head on q and k (`attn.norm`,
    the family `qk_norm`), % of the traced window's busy time; 0.0 where
    XLA fused all of it into a neighbour's pass."""
    return readers_step.family_pct(run, "qk_norm") if is_sdar(run) else None


def blockdiff_tiles_pct(run: dict) -> Optional[float]:
    """(q block, sub-tile) visits of the masked kernels a head, % of the
    visits of a causal walk over 2L rows and 2L keys: the step's own
    report (`diff_tiles_visited`, `diff_tiles_causal`)."""
    report = run.get("diffusion") or {}
    if not is_sdar(run) or not report.get("diff_tiles_causal"):
        return None
    return 100.0 * report["diff_tiles_visited"] / report["diff_tiles_causal"]
