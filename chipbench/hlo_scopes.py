"""Which `jax.named_scope` a device operation ran under.

trace_reduce.from_xplane keeps an op's own name (`fusion.166`) and drops
its scope; the compiled step's HLO text has both: every instruction
line names the instruction and carries `op_name="jit(step)/.../
moe.dispatch/gather"` in its metadata. `scopes_of` reads that text once
into {instruction name: scope}, for the scopes asked for; `seconds_by_scope`
sums a trace's leaf ops by it. A fusion carries its root's op_name, so
an op is attributed to one scope, whole.
"""

from __future__ import annotations

import re

from chipbench import trace_reduce as tr

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", re.M)


def scopes_of(hlo_text: str, scopes: tuple) -> dict:
    """{instruction name: the first of `scopes` found in its op_name};
    instructions under none of them are left out. A scope is matched as
    a whole path component (`moe.experts`, not `moe.experts2`)."""
    wanted = re.compile(r"(?:^|[/(])(" + "|".join(re.escape(s) for s in scopes) + r")(?:[/)]|$)")
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        m = wanted.search(op_name)
        if m:
            out[name] = m.group(1)
    return out


def seconds_by_scope(trace: tr.Trace, win: tuple, scope_of: dict) -> dict:
    """{scope: seconds of leaf ops inside the window, mean over devices}."""
    acc: dict = {}
    for evs in trace.device_ops.values():
        for name, s, d in tr.leaves(evs):
            scope = scope_of.get(name)
            if scope is not None and s + d > win[0] and s < win[1]:
                acc[scope] = acc.get(scope, 0.0) + tr.total(tr.clip([(s, s + d)], *win))
    n_dev = max(len(trace.device_ops), 1)
    return {k: v / n_dev for k, v in acc.items()}
