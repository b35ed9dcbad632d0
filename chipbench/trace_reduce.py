"""From a profiler trace to numbers: the one reduction every PR uses.

A trace is first brought into a plain structure (`Trace`: per device
its op events and its program events, and the host's events), either
from an `.xplane.pb` the JAX profiler wrote (`from_xplane`) or from a
hand-built dict (`from_dict`, the tests' fixture). Everything below
works on that structure only:

  busy_union        seconds in which at least one op ran on a device
  idle_gaps         the complement inside the window, longest first,
                    each named by what the host was doing in it
  class_time        device seconds per class of program or kernel; the
                    classes come from name patterns kept as DATA
                    (chipbench/trace_names/*.json)
  exposed           seconds of one class (collectives) during which no
                    op of another class ran on that device

Times are seconds on the profiler's own clock. Which lines of a device
plane hold ops and programs is also data ("lines" in trace_names).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Iterable, Optional

Event = tuple  # (name, start_s, dur_s)


@dataclasses.dataclass
class Trace:
    device_ops: dict       # device plane name -> [Event]; nested (a while holds its body's ops)
    device_programs: dict  # device plane name -> [Event]
    host: list             # [(thread/line name, name, start_s, dur_s)]
    device_async: dict = dataclasses.field(default_factory=dict)  # copies, collectives in flight


def from_dict(d: dict) -> Trace:
    return Trace(
        device_ops={k: [tuple(e) for e in v] for k, v in d["device_ops"].items()},
        device_programs={k: [tuple(e) for e in v]
                         for k, v in d.get("device_programs", {}).items()},
        host=[tuple(e) for e in d.get("host", [])],
        device_async={k: [tuple(e) for e in v] for k, v in d.get("device_async", {}).items()},
    )


def from_xplane(path: str, lines: dict) -> Trace:
    """`lines`: {"device_plane": regex, "ops": [line names], "programs":
    [line names], "async": [line names], "host_plane": regex, "kernel_mark":
    text that marks a Pallas kernel's op} from trace_names."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    dev_re, host_re = re.compile(lines["device_plane"]), re.compile(lines["host_plane"])
    ops, programs, host, asyncs = {}, {}, [], {}
    mark = lines.get("kernel_mark")
    for plane in data.planes:
        if dev_re.search(plane.name):
            for line in plane.lines:
                evs = [(short_name(e.name, mark), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events]
                if line.name in lines["ops"]:
                    ops.setdefault(plane.name, []).extend(evs)
                elif line.name in lines["programs"]:
                    programs.setdefault(plane.name, []).extend(evs)
                elif line.name in lines.get("async", ()):
                    asyncs.setdefault(plane.name, []).extend(evs)
        elif host_re.search(plane.name):
            for line in plane.lines:
                host.extend((line.name, e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events if e.duration_ns > 0)
    return Trace(ops, programs, host, asyncs)


def short_name(name: str, kernel_mark: Optional[str] = None) -> str:
    """An op event carries its whole HLO line ("%while.51 = (s32[]...")):
    keep the op's own name, and put `kernel:` before it when the line
    holds `kernel_mark` (the custom-call target of a Pallas kernel),
    since the op's own name (closed_call.7) says nothing of that."""
    short = name.split(" = ", 1)[0].lstrip("%")[:120]
    return "kernel:" + short if kernel_mark and kernel_mark in name else short


def leaves(events: list) -> list:
    """The events that hold no other event (a `while` or a call spans
    its body's ops on the same line: summing both counts time twice)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []   # stack of [event, has_child]
    for e in evs:
        # 1 ns of slack: an op that starts as its neighbour ends is no child of it
        while stack and e[1] >= stack[-1][0][1] + stack[-1][0][2] - 1e-9:
            done, has_child = stack.pop()
            if not has_child:
                out.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    out.extend(done for done, has_child in stack if not has_child)
    return out


# -- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[tuple]) -> list:
    """Sorted, disjoint [(start, end)] covering the same points."""
    out: list = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: list, b: list) -> list:
    """Points of union `a` not in union `b` (both sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events: list) -> list:
    return [(s, s + d) for _, s, d in events]


# -- the reductions -----------------------------------------------------------


def window(trace: Trace, marker: str = "chipbench.window") -> tuple:
    """(start, end) of the traced window: the harness's own host
    annotation `marker` when the trace has it, else the extent of the
    device events."""
    marks = [(s, s + d) for _, n, s, d in trace.host if n == marker]
    if marks:
        return min(a for a, _ in marks), max(b for _, b in marks)
    spans = [x for evs in trace.device_ops.values() for x in _spans(evs)]
    if not spans:
        raise ValueError("the trace holds no device operation")
    return min(a for a, _ in spans), max(b for _, b in spans)


def busy(trace: Trace, win: tuple) -> dict:
    """{"busy_s": mean over devices of the union of op intervals inside
    the window, "window_s", "per_device": {name: busy_s}}."""
    per = {dev: total(clip(union(_spans(evs)), *win))
           for dev, evs in trace.device_ops.items()}
    if not per:
        raise ValueError("the trace holds no device plane")
    return {"busy_s": sum(per.values()) / len(per), "window_s": win[1] - win[0],
            "per_device": per}


def classify(name: str, rules: list) -> Optional[str]:
    for rx, cls in rules:
        if rx.search(name):
            return cls
    return None


def class_time(events_by_device: dict, rules: list, win: tuple) -> dict:
    """{class: {"seconds": union time inside the window, mean over
    devices, "count": events per device, mean}}; unmatched events are
    class None and left out. Parents and children of one class merge in
    the union, so a kernel that spans its own sub-events counts once."""
    acc: dict = {}
    n_dev = max(len(events_by_device), 1)
    for evs in events_by_device.values():
        by_cls: dict = {}
        for name, s, d in evs:
            cls = classify(name, rules)
            if cls is not None and s + d > win[0] and s < win[1]:
                by_cls.setdefault(cls, []).append((s, s + d))
        for cls, spans in by_cls.items():
            a = acc.setdefault(cls, {"seconds": 0.0, "count": 0.0})
            a["seconds"] += total(clip(union(spans), *win)) / n_dev
            a["count"] += len(spans) / n_dev
    return acc


def exposed(trace: Trace, rules: list, cls: str, win: tuple) -> float:
    """Seconds, mean over devices, in which an op of class `cls` ran and
    no op of any other class did (a collective nothing hides)."""
    out = 0.0
    for dev, evs in trace.device_ops.items():
        ops = leaves(evs)
        flying = trace.device_async.get(dev, [])   # a collective from its start to its done
        mine = union(_spans([e for e in ops + flying if classify(e[0], rules) == cls]))
        rest = union(_spans([e for e in ops if classify(e[0], rules) != cls]))
        out += total(clip(subtract(mine, rest), *win))
    return out / max(len(trace.device_ops), 1)


def top_ops(trace: Trace, win: tuple, n: int = 10) -> list:
    """[[op name, seconds summed over its events, mean over devices]]."""
    acc: dict = {}
    for evs in trace.device_ops.values():
        for name, s, d in leaves(evs):
            if s + d > win[0] and s < win[1]:
                acc[name] = acc.get(name, 0.0) + d
    n_dev = max(len(trace.device_ops), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / n_dev] for k, v in ranked]


def idle_gaps(trace: Trace, win: tuple, n: int = 10, prefer: str = "chipbench.") -> list:
    """The n longest gaps with no op on the first device, each named by
    the host event that overlaps it most (one of the harness's own
    annotations where any overlaps): [[name, seconds]]."""
    if not trace.device_ops:
        return []
    dev = sorted(trace.device_ops)[0]
    gaps = subtract([win], clip(union(_spans(trace.device_ops[dev])), *win))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        best, best_key = "host:nothing recorded", (False, 0.0)
        for _, name, s, d in trace.host:
            ov = min(b, s + d) - max(a, s)
            if ov <= 0 or name == "chipbench.window":
                continue
            key = (name.startswith(prefer), ov)
            if key > best_key:
                best, best_key = name, key
        out.append([best, b - a])
    return out
