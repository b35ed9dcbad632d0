"""What the readers of a GLM-4.7-Flash share's cell have in common. Each
returns None where there is nothing to read (a run with no trace, a
program with no `mla.*` or `mtp.*` scope or no `pairs_elsewhere`
statistic: the parent of the PR that added them), so the line leaves
the metric out.

Device time is attributed through the compiled step's HLO text, as the
expert layer's and CCA's are (run["cca_scopes"]: every scope of the
configuration's `check.scopes` that is no `moe.*` one, set by
runners/train_reference_from_config.py). An instruction is attributed
to the OUTERMOST of the listed scopes in its path, so everything the
multi-token-prediction block runs (its own MLA, expert layer and shared
expert) reads `mtp.block`, and the `mla.*`, `shared.ffn` and `moe.*`
scopes read the dense and expert layers before it alone: the scopes
partition the step. A Pallas kernel is `kernel:<instruction>` in a
trace and `<instruction>` in the HLO text: `seconds_by_scope` here looks
it up by the latter, so the flash and grouped-matmul kernels of the MTP
block count with that block."""

from __future__ import annotations

from typing import Optional

from chipbench import costs, costs_glm_lite, hlo_scopes, readers
from chipbench.readers_moe import share_pct  # noqa: F401 - these readers' too
from chipbench.readers_zaya import FLASH_CLASSES, held_pairs

KERNEL = "kernel:"


def seconds_by_scope(run: dict) -> Optional[dict]:
    """{scope: seconds of leaf ops AND kernels inside the traced window,
    mean over devices}; None without a trace or where nothing ran under
    a listed scope."""
    scope_of = run.get("cca_scopes")
    if not run.get("trace") or not scope_of:
        return None
    with_kernels = {**scope_of, **{KERNEL + name: scope for name, scope in scope_of.items()}}
    return hlo_scopes.seconds_by_scope(run["trace"], run["win"], with_kernels) or None


def scope_share_pct(run: dict, prefix: str) -> Optional[float]:
    """Device time under the scopes that start with `prefix`, % of busy time."""
    scoped = seconds_by_scope(run)
    if scoped is None or not any(k.startswith(prefix) for k in scoped):
        return None
    return share_pct(run, sum(v for k, v in scoped.items() if k.startswith(prefix)))


def flash_roofline(run: dict) -> Optional[float]:
    spent = sum(readers.class_seconds(run, "ops", c) or 0.0 for c in FLASH_CLASSES)
    shape = run.get("shape") or {}
    if not spent or "kv_lora_rank" not in shape:
        return None
    c = costs_glm_lite.flash_cost(shape, shape["train"]["global_batch"] / run["chips"],
                                  run["traffic"]["seq_len"])
    n = costs_glm_lite.blocks(shape)["attention"] * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def expert_matmul_roofline_held(run: dict) -> Optional[float]:
    spent = readers.class_seconds(run, "ops", "expert_matmul")
    pairs = held_pairs(run)
    shape = run.get("shape") or {}
    if not spent or pairs is None or "kv_lora_rank" not in shape:
        return None
    n_blocks = costs_glm_lite.blocks(shape)["expert"]
    # the held rows of a step, spread over its blocks: operations are linear in the
    # rows and every block moves its own weights, so the mean block times their number
    c = costs_glm_lite.grouped_matmul_cost(shape, pairs["held"] / run["chips"] / n_blocks)
    n = n_blocks * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent


def train_mfu_pct(run: dict) -> Optional[float]:
    rate = (run.get("values") or {}).get("train_tok_s")
    pairs = held_pairs(run)
    if not rate or pairs is None or "kv_lora_rank" not in (run.get("shape") or {}):
        return None
    per_token = costs_glm_lite.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], pairs["held"] / pairs["all"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
