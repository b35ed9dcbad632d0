"""What the readers of the step's HOST timeline share (PR 51).

The program keeps, in every run, traced or not, a row a train step
(`ray_tpu.obs.step_timeline`: the step's call, the caller's wait, the
report, the time between, the thread's and the other threads' CPU, the
collector's and the compiler's seconds) and names the slow ones
(`obs.slow_steps`). These readers take the WINDOW's rows: those whose
`start` lies within `run["window_wall"]`, the recorder's clock being
`time.time()` as the runner's window edges are. A program without the
timeline (the parent of PR 51) gives None, and the line leaves the
metric out.

Two corrections, both the runner's own made again on the program's rows:

  * the last step of the window has no next step inside it (what calls
    the step next is a check after the window, minutes later): its
    period, its `between_s` and its clocks are blanked, and it counts in
    no median and as no stall;
  * in a traced run the runner starts and stops the profiler between two
    steps and takes those seconds off its own clock (`paused`); they fall
    in a row's `between_s`. `run["steps"][k]["start"]` is the runner's
    paused clock at step k and `rows[k]["start"] - window_wall[0]` the
    unpaused one, so the growth of their difference from step k to k + 1
    is the pause inside row k: it is taken off `period_s` and `between_s`,
    the row is marked `paused_s`, and its CPU clocks (the profiler's own
    work) are blanked. Only in a traced run, and only a growth over
    PAUSE_MIN_S: the difference also carries the batch maker's jitter,
    and a stall of the batch maker in an untraced run is a stall.

The device's gaps (`step_gaps`) are the trace's alone: on the first
device, from the last operation inside one train-step program to the
first operation inside the next.
"""

from __future__ import annotations

import statistics
from typing import Optional

from chipbench import trace_reduce as tr
from chipbench.readers_step import STEP_PROGRAM  # the class trace_names gives the step's program

PAUSE_MIN_S = 0.02
SEGMENTS = ("dispatch_s", "wait_s", "report_s", "between_s")
PROGRAM_SPANS = ("train.step", "train.report")
CLOCKS = ("thread_cpu_s", "other_cpu_s", "nivcsw", "gc_s")


def window_rows(run: dict) -> Optional[list]:
    """The window's rows, corrected as above; made once a run."""
    if "timeline_rows" in run:
        return run["timeline_rows"]
    run["timeline_rows"] = None
    if run.get("kind") != "train" or not run.get("window_wall"):
        return None
    from ray_tpu import obs

    timeline = getattr(obs, "step_timeline", None)
    if timeline is None:
        return None
    w0, w1 = run["window_wall"]
    rows = timeline(w0, w1)
    if not rows:
        return None
    for r in rows:
        if r["period_s"] is not None and r["start"] + r["period_s"] > w1:
            r.update(period_s=None, between_s=None, **dict.fromkeys(CLOCKS))
    steps = run.get("steps") or []
    if run.get("traced_steps") and len(steps) == len(rows):
        lag = [r["start"] - w0 - s["start"] for r, s in zip(rows, steps)]
        for k in range(len(rows) - 1):
            paused = lag[k + 1] - lag[k]
            if paused > PAUSE_MIN_S and rows[k]["period_s"] is not None:
                rows[k]["period_s"] -= paused
                rows[k]["between_s"] -= paused
                rows[k].update(paused_s=paused, **dict.fromkeys(CLOCKS))
    run["timeline_rows"] = rows
    return rows


def stalls(run: dict) -> Optional[list]:
    """`obs.slow_steps` of the window's rows: [] where none was slow,
    None without a timeline."""
    rows = window_rows(run)
    if rows is None:
        return None
    from ray_tpu import obs

    return obs.slow_steps(rows=rows)


def window_seconds(rows: list) -> float:
    """The seconds the window's timed rows span: the sum of their periods."""
    return sum(r["period_s"] for r in rows if r["period_s"] is not None)


def median_ms(run: dict, key: str) -> Optional[float]:
    rows = window_rows(run)
    values = [r[key] for r in rows or () if r[key] is not None]
    return 1e3 * statistics.median(values) if values else None


def report_max_ms(run: dict) -> Optional[float]:
    reports = [r["report_s"] for r in window_rows(run) or () if r["report_s"] is not None]
    return 1e3 * max(reports) if reports else None


def stall_loss_pct(run: dict) -> Optional[float]:
    slow = stalls(run)
    if slow is None:
        return None
    seconds = window_seconds(window_rows(run))
    return 100.0 * sum(s["excess_s"] for s in slow) / seconds if seconds > 0 else None


def gc_pause_ms(run: dict) -> Optional[float]:
    """The collector's seconds inside the window: the growth of
    `host.gc`'s counter from step entry to step entry, every generation."""
    rows = window_rows(run)
    values = [r["gc_s"] for r in rows or () if r["gc_s"] is not None]
    return 1e3 * sum(values) if values else None


def other_cpu_pct(run: dict) -> Optional[float]:
    """CPU seconds of the process's threads other than the loop's, over
    the seconds of the rows that have the clock (a row the profiler
    paused in has not)."""
    rows = [r for r in window_rows(run) or () if r["other_cpu_s"] is not None]
    seconds = window_seconds(rows)
    return 100.0 * sum(r["other_cpu_s"] for r in rows) / seconds if seconds > 0 else None


def step_gaps(run: dict) -> Optional[list]:
    """[(start, end)] on the profiler's clock: on the first device, from
    the last operation inside one train-step program to the first inside
    the next, for every two consecutive ones in the trace. None without a
    trace or with fewer than two such programs."""
    trace, rules = run.get("trace"), run.get("rules")
    if trace is None or not rules or not trace.device_ops:
        return None
    dev = sorted(trace.device_ops)[0]
    programs = sorted((s, s + d) for name, s, d in trace.device_programs.get(dev, ())
                      if tr.classify(name, rules) == STEP_PROGRAM)
    ops = tr.union((s, s + d) for _, s, d in trace.device_ops[dev])
    edges = []  # (first operation's start, last operation's end) of each program
    for a, b in programs:
        inside = tr.clip(ops, a, b)
        if inside:
            edges.append((inside[0][0], inside[-1][1]))
    gaps = [(edges[k][1], edges[k + 1][0]) for k in range(len(edges) - 1)]
    return gaps or None


def step_gap_ms(run: dict) -> Optional[float]:
    gaps = step_gaps(run)
    return 1e3 * statistics.median(b - a for a, b in gaps) if gaps else None


def step_gap_program_pct(run: dict) -> Optional[float]:
    """Of those gaps' seconds, the share the program's own host events
    (`train.step`, `train.report`: the profiler's clock, as the gaps) cover.
    None where the trace holds no `train.step` event: the program has not
    the span."""
    gaps = step_gaps(run)
    if not gaps:
        return None
    host = run["trace"].host
    if not any(name == PROGRAM_SPANS[0] for _, name, _, _ in host):
        return None
    mine = tr.union((s, s + d) for _, name, s, d in host if name in PROGRAM_SPANS)
    covered = sum(tr.total(tr.clip(mine, a, b)) for a, b in gaps)
    return 100.0 * covered / sum(b - a for a, b in gaps)
