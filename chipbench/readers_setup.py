"""What the set-up readers share: the program's compile log and layer counters, read
through ray_tpu.obs. A program that has neither (the parent of PR 24) gives None, and the
line leaves the metric out."""

from __future__ import annotations

from typing import Optional


def compiles_before_window(run: dict) -> Optional[list]:
    """Entries (ended_at, program, seconds, "compiled" | "loaded") of the program's compile
    log that ended before the window opened; None where there is no log or it is empty."""
    from ray_tpu import obs

    log = getattr(obs, "compile_log", None)
    entries = log() if log is not None else []
    if not entries:
        return None
    return [e for e in entries if e[0] < run["window_wall"][0]]


def layer_busy_s(names: tuple) -> Optional[float]:
    """Busy seconds summed over the layer spans `names`; None where none of them was used."""
    from ray_tpu import obs

    counters = getattr(obs, "layer_counters", None)
    got = counters() if counters is not None else {}
    found = [got[n]["busy_s"] for n in names if n in got]
    return sum(found) if found else None
