"""What the set-up readers share: the program's compile log and layer counters, read
through ray_tpu.obs, and the harness's own table of start-up phases (chipbench/phases.py,
carried in the runner's `done` report). A program that has no log or counters (the parent
of PR 24) or a run without the table (the parent of PR 31) gives None, and the line leaves
the metric out."""

from __future__ import annotations

from typing import Optional

from chipbench import phases

RUNTIME_SPANS = ("runtime.init", "train.worker_start")


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


def phase_s(run: dict, name: str) -> Optional[float]:
    """Seconds of the start-up phase `name`, on setup_s's own clock; None without the table."""
    return (run.get("setup_phases") or {}).get(name)


def unnamed_s(run: dict) -> Optional[float]:
    """setup_s less every named phase inside its clock (the machine's phases are outside
    it: chipbench/phases.py) and less the runtime's spans; the compile log's seconds lie
    inside the phases and are not taken off again. None without the table."""
    table = run.get("setup_phases")
    if not table:
        return None
    named = sum(table.get(name, 0.0) for name in phases.PROGRAM)
    return run["values"]["setup_s"] - named - (layer_busy_s(RUNTIME_SPANS) or 0.0)
