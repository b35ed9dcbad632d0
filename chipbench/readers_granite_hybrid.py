"""What the readers of a Granite 4.0-H cell have in common. Each returns
None where there is nothing to read (a run with no trace, a program with
no `ssm.*` scope or no such model: the parent of the PR that added them; a
run whose runner left no `packed` record), so the line leaves the metric
out.

Device time is read from the step's table (readers_step.py: every
operation of the traced window booked to the INNERMOST listed scope of
its path; chipbench/step_scopes/nemotron_h.json's families `ssm_proj`,
`ssm_scan` and `ssm_glue` are this model's too: it runs that module's
Mamba sublayer). The attention layer's flash kernels take the name of
their scope (`kernel:attn.attend.N`). The same rules as
readers_nemotron_h.py's, under names of this cell's own
(`ssm_*.g1`: ONE group of 64 heads), because the accepted `ssm_*`
entries are held to `twotower-train-8k` alone by that cell's test."""

from __future__ import annotations

from typing import Optional

from chipbench import costs_granite_hybrid, readers_nemotron_h, readers_step
from chipbench.readers_laguna import kernel_seconds
# the sibling cell's own arithmetic, as it stands: a cost against the traced steps' roofline,
# and a chip's share of the batch
from chipbench.readers_nemotron_h import FAMILIES, _least_pct, _per_chip


def is_granite_hybrid(run: dict) -> bool:
    return "mamba_n_groups" in (run.get("shape") or {})


def families_pct(run: dict, families: tuple = FAMILIES) -> Optional[float]:
    """Device time of the families together, % of the traced window's
    busy time (readers_nemotron_h.py's rule); None in another model's run
    and where nothing ran under any `ssm*` family."""
    return readers_nemotron_h.families_pct(run, families) if is_granite_hybrid(run) else None


def visible_pairs(run: dict) -> Optional[float]:
    """The attention layer's visible (query, key) pairs a head and step,
    the mean of the traced steps' own batches."""
    return (run.get("packed") or {}).get("visible_pairs_traced")


def scan_roofline(run: dict) -> Optional[float]:
    """Everything under `ssm.scan` (every Mamba layer: the two scan
    kernels, the kernel run again under the block's remat, and the [heads,
    S] arithmetic of dt A) against the larger of operations / peak FLOP/s
    and bytes / peak bytes/s of the position-by-position scan."""
    table = readers_step.step_table(run) if is_granite_hybrid(run) else None
    row = None if table is None else table["scopes"].get("ssm.scan")
    if row is None or not row["seconds"]:
        return None
    return _least_pct(run, costs_granite_hybrid.scan_cost(run["shape"], *_per_chip(run)),
                      row["seconds"])


def flash_roofline(run: dict) -> Optional[float]:
    """The `attn.attend.N` kernels (the attention layer's, forward, the
    forward run again under remat, and backward) against the flash cost of
    the pairs the traced batches' documents leave visible."""
    pairs = visible_pairs(run)
    if not is_granite_hybrid(run) or pairs is None:
        return None
    spent = kernel_seconds(run, "attn.attend")
    if not spent:
        return None
    batch, seq = _per_chip(run)
    return _least_pct(run, costs_granite_hybrid.flash_cost(run["shape"], batch, seq, pairs / batch),
                      spent)


def train_mfu_pct(run: dict) -> Optional[float]:
    """train_tok_s x the operations a token requires (the scores over the
    window's own visible pairs) over chips x peak FLOP/s."""
    rate = (run.get("values") or {}).get("train_tok_s")
    seen = (run.get("packed") or {}).get("visible_pairs")
    if not rate or not seen or not is_granite_hybrid(run):
        return None
    batch = run["shape"]["train"]["global_batch"]
    per_token = costs_granite_hybrid.train_flops_per_token(
        run["shape"], run["traffic"]["seq_len"], sum(seen) / len(seen) / batch)
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
