"""One reading of a train step's HOST side, for an operator and for the
benchmark alike, from what the recorder always keeps: the timelines of
`train.step` (the step's call: obs/programs.py) and `train.report`
(train/session.py), the collector's pauses (`host.gc`) and the compile
log. Nothing here records; a run nobody traced answers the same.

A step's period, from one `train.step` entry to the next, is four
segments on the caller's thread:

    dispatch_s   inside the step's call: arguments flattened, the program
                 enqueued (traced, compiled or loaded at a first call)
    wait_s       the call's end to the next `train.report`'s start: the
                 caller's wait for the device, whatever it does there
    report_s     inside `session.report`
    between_s    the report's end to the next `train.step` entry: the
                 caller's input side

A step with no report before the next step has `wait_s` and `report_s`
None and everything after its call in `between_s`. The last step kept
has no next entry: its `period_s`, `between_s` and the clocks' deltas
are None.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Optional

from ray_tpu.obs.recorder import GC_NAME, SpanRecorder, get_recorder

STEP, REPORT = "train.step", "train.report"
SEGMENTS = ("dispatch_s", "wait_s", "report_s", "between_s")


def _overlap(intervals: list, lo: float, hi: float) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in intervals)


def step_timeline(since: float = 0.0, until: Optional[float] = None,
                  recorder: Optional[SpanRecorder] = None) -> list:
    """A row a `train.step` call that started in [since, until]
    (time.time()), oldest first: `start`, the four segments and
    `period_s` (above); over the period `thread_cpu_s` (CPU seconds of
    the calling thread), `other_cpu_s` (of the process's OTHER threads:
    the runtime's scheduler, actor and telemetry threads, XLA's),
    `nivcsw` (times the kernel took the calling thread off a core; None
    where the platform cannot say) and `gc_s` (seconds inside the
    collector, every generation), all None where the next step was
    called from another thread; `gc_generation` (the oldest generation
    among the collections kept in `host.gc`'s timeline that overlap the
    period, None without one) and `compile_s` (seconds of the compile
    log's entries, compiles and cache loads, that overlap it)."""
    from ray_tpu.obs import compile_log

    rec = recorder if recorder is not None else get_recorder()
    # by start: the rings are in the order the spans ENDED, and two workers of one
    # process (threads of the in-process runtime) share them
    steps = sorted(rec.layer_timeline(STEP), key=lambda e: e[0])
    reports = sorted(rec.layer_timeline(REPORT), key=lambda e: e[0])
    report_starts = [r[0] for r in reports]
    pauses = rec.layer_timeline(GC_NAME)  # one collection at a time: in order, disjoint
    pause_ends = [p[1] for p in pauses]
    first = steps[0][0] if steps else 0.0
    compiles = [(ended - seconds, ended) for ended, _, seconds, _ in compile_log(first)]
    rows = []
    for k, (start, end, clocks) in enumerate(steps):
        if start < since or (until is not None and start > until):
            continue
        following = steps[k + 1] if k + 1 < len(steps) else None
        horizon = following[0] if following is not None else float("inf")
        row = {"start": start, "dispatch_s": end - start, "wait_s": None, "report_s": None,
               "between_s": None, "period_s": None, "thread_cpu_s": None,
               "other_cpu_s": None, "nivcsw": None, "gc_s": None, "gc_generation": None}
        j = bisect.bisect_left(report_starts, end)
        done = end
        if j < len(reports) and reports[j][0] < horizon:
            r0, r1, _ = reports[j]
            row["wait_s"], row["report_s"], done = r0 - end, r1 - r0, r1
        if following is not None:
            row["period_s"], row["between_s"] = horizon - start, horizon - done
            then = following[2]
            if clocks and then and clocks[0] == then[0]:
                row["thread_cpu_s"] = then[1] - clocks[1]
                row["other_cpu_s"] = max(0.0, (then[2] - clocks[2]) - row["thread_cpu_s"])
                if clocks[3] is not None and then[3] is not None:
                    row["nivcsw"] = then[3] - clocks[3]
                row["gc_s"] = then[4] - clocks[4]
        i = bisect.bisect_right(pause_ends, start)
        while i < len(pauses) and pauses[i][0] < horizon:
            row["gc_generation"] = max(row["gc_generation"] or 0, pauses[i][2]["generation"])
            i += 1
        row["compile_s"] = _overlap(compiles, start, horizon)
        rows.append(row)
    return rows


def _median_of(rows: list, key: str) -> float:
    values = [r[key] for r in rows if r.get(key) is not None]
    return statistics.median(values) if values else 0.0


def slow_steps(since: float = 0.0, until: Optional[float] = None, factor: float = 1.2,
               rows: Optional[list] = None, recorder: Optional[SpanRecorder] = None) -> list:
    """The rows (of `step_timeline(since, until)`, or `rows` as given: a
    reader that has taken a known pause off its rows hands them in)
    whose period is over `factor` x the median period, each with
    `median_s`, `excess_s` (period less median), `segment` (the one of
    the four that grew most against its own median) and ONE `cause`,
    the first of this order that holds:

        compile        a compile or a cache load overlaps the step
        gc             the collector's seconds, over a median step's,
                       cover half the excess or more
        report | dispatch | between
                       that segment grew by more than half the excess
                       and no pause above explains it
        preempted      the excess is in `wait_s`, the calling thread's
                       CPU time stood still (it grew by less than a
                       tenth of the excess) and the kernel took the
                       thread off a core more often than in a median step
        other_threads  the process's other threads burned at least the
                       excess more CPU than in a median step
        wait           none of these: the device took longer, or a
                       wake-up nobody recorded came late. The host
                       cannot tell those two apart, and this does not
                       guess."""
    if rows is None:
        rows = step_timeline(since, until, recorder)
    timed = [r for r in rows if r["period_s"] is not None]
    if len(timed) < 2:
        return []
    median = statistics.median(r["period_s"] for r in timed)
    usual = {key: _median_of(timed, key)
             for key in SEGMENTS + ("thread_cpu_s", "other_cpu_s", "nivcsw", "gc_s")}
    out = []
    for r in timed:
        if r["period_s"] <= factor * median:
            continue
        excess = r["period_s"] - median
        grew = {seg: r[seg] - usual[seg] for seg in SEGMENTS if r[seg] is not None}
        segment = max(grew, key=grew.get)

        def over(key: str) -> Optional[float]:
            return None if r[key] is None else r[key] - usual[key]

        if r["compile_s"] > 0:
            cause = "compile"
        elif (over("gc_s") or 0.0) >= 0.5 * excess:
            cause = "gc"
        elif segment != "wait_s" and grew[segment] > 0.5 * excess:
            cause = segment[:-2]
        elif (segment == "wait_s" and over("thread_cpu_s") is not None
              and over("thread_cpu_s") < 0.1 * excess and (over("nivcsw") or 0) > 0):
            cause = "preempted"
        elif (over("other_cpu_s") or 0.0) >= excess:
            cause = "other_threads"
        else:
            cause = "wait"
        out.append({**r, "median_s": median, "excess_s": excess, "segment": segment,
                    "cause": cause})
    return out
