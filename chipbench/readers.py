"""What the per-layer readers share. A reader (chipbench/layer_metrics/
<name>.py) is `read(run) -> number or None`: `run` is what a runner
returns, and a reader that finds nothing to read returns None, so the
harness leaves that metric out of the line."""

from __future__ import annotations

from typing import Optional

from chipbench import costs


def idle_pct(run: dict) -> Optional[float]:
    busy = run.get("busy")
    if not busy or busy["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])


def hbm_peak_gib(run: dict) -> Optional[float]:
    peak = run.get("memory_peak_bytes")
    return None if not peak else peak / 2 ** 30


def class_seconds(run: dict, level: str, cls: str) -> Optional[float]:
    """Device seconds of one class among the trace's programs or ops."""
    got = (run.get(level) or {}).get(cls)
    return None if not got else got["seconds"]


def flash_roofline(run: dict) -> Optional[float]:
    """Forward + backward flash kernel time in the traced steps against
    the larger of operations / peak FLOP/s and bytes / peak bytes/s."""
    spent = sum(class_seconds(run, "ops", c) or 0.0 for c in ("flash_fwd", "flash_bwd", "flash"))
    if not spent:
        return None
    shape = run["shape"]
    per_chip_batch = shape["train"]["global_batch"] / run["chips"]
    c = costs.flash_cost(shape, per_chip_batch, run["traffic"]["seq_len"])
    n = shape["num_hidden_layers"] * run["traced_steps"]
    least, _ = costs.roofline_seconds(
        n * (c["fwd_flops"] + c["bwd_flops"]), n * (c["fwd_bytes"] + c["bwd_bytes"]),
        run["peaks"])
    return 100.0 * least / spent
