"""Trainer: median milliseconds of the program's `train.report` span (inside
session.report, on the worker) among the host events of the traced steps. The event is
written by the program, not the harness: its being in run["trace"].host at all is the proof
that program spans share the device trace's clock. None without a trace or without the span."""

import statistics


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    durations = [d for _, name, _, d in trace.host if name == "train.report"]
    return 1e3 * statistics.median(durations) if durations else None
