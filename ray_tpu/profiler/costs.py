"""Static cost model: FLOPs and bytes-accessed per compiled segment.

Costs come from XLA's own compiler estimate —
``jax.jit(fn).lower(*args).compile().cost_analysis()`` — so they track
the program XLA actually emits (remat re-computation, fused epilogues,
layout copies), not a hand-derived formula. The chip-peak table — the
only one in the tree — turns those counts into roofline coordinates. An
accelerator it does not list is an error; the CPU backend gets a
labelled nominal row so tier-1 tests run under ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

# bf16 peak matmul FLOP/s and HBM bandwidth (bytes/s) by device
# generation: the published per-chip figures (Google Cloud TPU docs).
CHIP_PEAKS = [
    # (device_kind substring, flops/s, HBM bytes/s)
    ("v5 lite", 197e12, 819e9),
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v6 lite", 918e12, 1640e9),
    ("v6e", 918e12, 1640e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
]

# Nominal CPU peaks: a laptop-class core's ~1 TFLOP/s and ~50 GB/s
# memory bus. Deliberately round numbers — the CPU profile is for
# exercising the machinery, not for publishing attainment.
CPU_PEAKS = (1e12, 50e9)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    device_kind: str
    flops: float      # peak FLOP/s
    hbm_bytes_s: float  # peak memory bandwidth, bytes/s
    nominal: bool = False  # the CPU stand-in row, not a published peak

    @property
    def ridge_intensity(self) -> float:
        """FLOPs/byte above which the chip is compute-bound."""
        return self.flops / self.hbm_bytes_s


def chip_peaks(device=None) -> ChipPeaks:
    """Peak table lookup for a jax device (default: devices()[0]).

    Raises for an accelerator whose ``device_kind`` the table does not
    list: a utilization against a made-up peak is not a measurement."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = device.device_kind
    if device.platform == "cpu":
        return ChipPeaks(kind, *CPU_PEAKS, nominal=True)
    low = kind.lower()
    for key, fl, bw in CHIP_PEAKS:
        if key in low:
            return ChipPeaks(kind, fl, bw)
    raise ValueError(
        f"no peak FLOP/s / bandwidth row for device_kind {kind!r} "
        f"(platform {device.platform!r}); add it to CHIP_PEAKS with its source"
    )


@dataclasses.dataclass
class SegmentCost:
    """Compiler-estimated cost of one compiled program."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    populated: bool = False
    raw: Optional[dict] = None

    def minus(self, other: "SegmentCost") -> "SegmentCost":
        """Ladder difference (clamped at 0: XLA may fuse a later rung
        tighter than an earlier one)."""
        return SegmentCost(
            flops=max(0.0, self.flops - other.flops),
            bytes_accessed=max(0.0, self.bytes_accessed - other.bytes_accessed),
            populated=self.populated and other.populated,
        )


def _flatten_cost_analysis(ca: Any) -> Optional[dict]:
    """cost_analysis() shape varies by jax version: a dict, or a list of
    per-computation dicts (one per partition). Merge to one dict."""
    if ca is None:
        return None
    if isinstance(ca, dict):
        return dict(ca)
    if isinstance(ca, (list, tuple)):
        merged: dict = {}
        for entry in ca:
            if not isinstance(entry, dict):
                continue
            for k, v in entry.items():
                try:
                    merged[k] = merged.get(k, 0.0) + float(v)
                except (TypeError, ValueError):
                    continue
        return merged or None
    return None


def cost_from_compiled(compiled) -> SegmentCost:
    """Pull XLA's cost estimate from an already-compiled jax.stages
    Compiled object (never raises: a cost model must not take down the
    measurement path)."""
    try:
        raw = _flatten_cost_analysis(compiled.cost_analysis())
    except Exception:  # noqa: BLE001 — backend without cost analysis
        return SegmentCost()
    if not raw:
        return SegmentCost()
    return SegmentCost(
        flops=float(raw.get("flops", 0.0)),
        bytes_accessed=float(raw.get("bytes accessed", 0.0)),
        populated=True,
        raw=raw,
    )


def compiled_cost(fn: Callable, *args, donate_argnums=()) -> SegmentCost:
    """Lower + compile ``fn`` for ``args`` and pull XLA's cost estimate.

    Prefer cost_from_compiled when a compiled executable already exists
    (profile_segments does — compiling twice doubles a 400M-model
    profile's compile wall time for no new information).
    """
    import jax

    try:
        compiled = (
            jax.jit(fn, donate_argnums=donate_argnums).lower(*args).compile()
        )
    except Exception:  # noqa: BLE001
        return SegmentCost()
    return cost_from_compiled(compiled)
