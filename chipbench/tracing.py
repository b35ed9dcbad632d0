"""Taking a short profiler trace and reducing it (runner-side glue over
trace_reduce.py). Only the process that holds the chip can trace it."""

from __future__ import annotations

import glob
import os
import shutil

from chipbench import trace_reduce as tr


def start(trace_dir: str) -> None:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events: they swamp the file
    opts.host_tracer_level = 2     # TraceMe/TraceAnnotation spans of the host
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(trace_dir: str, names: dict):
    """-> (Trace, bytes of the file) or (None, None). The raw trace is
    deleted once read: tens of MB a run, and what comes back from the
    chip is capped."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None, None
    out = tr.from_xplane(files[0], names["lines"]), os.path.getsize(files[0])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def reduce(trace_dir: str, names: dict, log) -> dict:
    """-> {"trace", "win", "busy", "breakdown", "programs", "ops"} or
    {"busy": None} when the trace holds no device operation."""
    trace, size = load(trace_dir, names)
    if trace is None:
        log(event="trace", error="no .xplane.pb was written")
        return {"busy": None}
    if not any(trace.device_ops.values()):
        log(event="trace", error="no device operation in the trace",
            bytes=size)
        return {"busy": None}
    win = tr.window(trace)
    rules = names["rules"]
    out = {
        "trace": trace, "win": win, "rules": rules, "busy": tr.busy(trace, win),
        "programs": tr.class_time(trace.device_programs, rules, win),
        # every event of a class, parents too: the union inside class_time keeps a
        # kernel that spans its own sub-events from being counted twice or dropped
        "ops": tr.class_time(trace.device_ops, rules, win),
        "breakdown": {"device_ops": tr.top_ops(trace, win),
                      "idle_gaps": tr.idle_gaps(trace, win)},
    }
    # a digest for whoever writes the next name patterns: the programs
    # and ops by time, with how each was classified
    seen: dict = {}
    for evs in trace.device_programs.values():
        for name, _, d in evs:
            seen[name] = seen.get(name, 0.0) + d
    log(event="trace", bytes=size, window_s=win[1] - win[0],
        busy=out["busy"], programs_by_class=out["programs"], ops_by_class=out["ops"],
        programs=[[n, round(s, 6), tr.classify(n, rules)]
                  for n, s in sorted(seen.items(), key=lambda kv: -kv[1])[:12]],
        top_ops=[[n, round(s, 6), tr.classify(n, rules)]
                 for n, s in out["breakdown"]["device_ops"]])
    return out
