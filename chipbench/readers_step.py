"""The step's table: every device operation of the traced window under a
name of the model's own, and what the readers of it share.

A trace names an operation as XLA does (`fusion.166`); the program keeps
the record that finds the compiled step again (`ray_tpu.obs.op_names()`:
{instruction: [(opcode, op_name path, operands), ...]}, for a fusion the
instructions of its fused computation after its own). A program without
the record (the parent of the PR that added it) or a run without a trace
gives None, and the line leaves the metric out.

The names themselves (the scopes, the family each is summed into, which
of them is shallow) are data: chipbench/step_scopes/*.json, merged. A PR
that brings a kind of block adds a file there with its scopes.

THE ATTRIBUTION, stated once:

  * an operation of the traced window is booked to ONE scope, so the
    scopes, grouped into families, and the unscoped rest sum to the
    device's busy time;
  * a path's scope is the INNERMOST of the listed scopes in it (the MTP
    block's own MLA reads `mla.*`, not `mtp.block`); `tp_overlap.*` is
    not listed, so a ring reads the `attn.*` / `dense.ffn` it sits in;
  * a SHALLOW scope (`block.stack`, around the layer scan) holds only what
    stands directly in it: the scan's own slices of the stacked weights
    and residuals, its stacked writes, its zero fills and its control
    (`.../block.stack/while/body/dynamic_slice`). An operation deeper
    inside it (`.../while/body/closed_call/mul`) under no other listed
    scope is a name the model lacks, and is UNSCOPED: the stack is no
    catch-all, and a block kind without names cannot read well;
  * a fusion is booked to the scope of the matmul it holds (a `dot` or
    `convolution` of its fused computation, one outside `optim` before
    one inside), else to its root's (the fusion's own path), else, where
    the root stands under no listed scope, to the scope most of its
    instructions carry: XLA fuses the norms into the projections and
    every weight gradient into AdamW's update, and a root-only rule
    would call the head's weight gradient "optim" in one compile and
    "head" in the next;
  * a fusion that holds a matmul under a model scope AND instructions
    under `optim` is booked to the model scope and ALSO counted apart
    (`fused_with_optim_s`), so a reader of the head's share sees how
    much of it carries the update;
  * an instruction the compiler made itself has no path of the program's
    (none at all, or a bare `broadcast.95` with no `/` in it: the
    f32-to-bf16 convert of a layer stack's weights hoisted out of the
    scan, a zero fill, a layout copy, a prefetch's done). It is booked,
    last, to what its result is FOR: the scope of the nearest operation
    that reads it (through the loop's tuple, which the record has looked
    through, and through the shallow scope's slices) and is booked to a
    scope that is not shallow; so the expert weights' convert reads
    `moe.experts` whether the compiler hoisted it or not. Where only a
    shallow scope reads it, that one; where nothing does, unscoped;
  * a `while` or a call spans its body's operations on the same line:
    what they leave uncovered of it (the loop's own control) is booked to
    the `while` itself, so nothing of the busy time is left out;
  * a Pallas kernel is `kernel:<instruction>` in a trace and is looked
    up under its instruction; an operation the compiled text does not
    know at all, or one that ran in another program of the window (the
    harness's batch maker), is unscoped (and listed under `unknown`), so
    a record of the wrong program cannot read well.
"""

from __future__ import annotations

import os
import re
from typing import Optional

from chipbench import manifest as mf, trace_reduce as tr


def vocabulary(root: str = mf.ROOT) -> dict:
    """Merge of chipbench/step_scopes/*.json, files in name order: a later
    file adds families, adds scopes to a family, and wins a plain key."""
    merged: dict = {"families": {}, "shallow": {}, "engage_counters": {}}
    d = os.path.join(root, "chipbench", "step_scopes")
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        data = mf.read_json(root, f"chipbench/step_scopes/{fn}")
        for fam, scopes in data.pop("families", {}).items():
            merged["families"].setdefault(fam, [])
            merged["families"][fam] += [s for s in scopes if s not in merged["families"][fam]]
        for key in ("shallow", "engage_counters"):
            merged[key].update(data.pop(key, {}))
        merged.update(data)
    return merged


VOCABULARY = vocabulary()
FAMILY_OF = {scope: fam for fam, scopes in VOCABULARY["families"].items() for scope in scopes}
SCOPES = tuple(FAMILY_OF)
SHALLOW = {scope: frozenset(parts) for scope, parts in VOCABULARY["shallow"].items()}
MATMULS = tuple(VOCABULARY["matmuls"])
STEP_PROGRAM = VOCABULARY["program"]  # the class trace_names gives the train step's program
KERNEL = "kernel:"
UNSCOPED = "unscoped"
LOOKED_THROUGH = ("tuple", "while")  # the record's operands already lead past them
# a scope as a whole component of a path, bare or wrapped (`transpose(jvp(head))`)
_LISTED = re.compile(r"(?<![^/(])(" + "|".join(re.escape(s) for s in SCOPES) + r")(?![^/)])")


def family(scope: Optional[str]) -> str:
    """The family a scope is summed into (chipbench/step_scopes)."""
    return UNSCOPED if scope in (None, UNSCOPED) else FAMILY_OF[scope]


def instruction_of(operation: str) -> str:
    """A trace's operation under the name the compiled text has for it."""
    return operation[len(KERNEL):] if operation.startswith(KERNEL) else operation


def scope_of_path(path: str) -> Optional[str]:
    found = None
    for found in _LISTED.finditer(path):
        pass
    if found is None:
        return None
    scope = found.group(1)
    if scope in SHALLOW:
        # `))/while/body/dynamic_slice`: every part but the primitive is the loop's own
        between = path[found.end():].lstrip(")").split("/")[:-1]
        if any(part and part not in SHALLOW[scope] for part in between):
            return None
    return scope


def book(entries: list) -> tuple:
    """(scope or None, whether a matmul of the model is fused with the
    optimizer's update) of one instruction's `op_names()` entries, by
    what the instruction itself carries."""
    inner = [(op, scope_of_path(path)) for op, path, _ in entries[1:]]
    matmuls = [s for op, s in inner if op in MATMULS and s is not None]
    model = [s for s in matmuls if s != "optim"]
    if model:
        return model[0], any(s == "optim" for _, s in inner)
    if matmuls:
        return matmuls[0], False
    own = scope_of_path(entries[0][1])
    if own is not None:
        return own, False
    carried = [s for _, s in inner if s is not None]
    return (max(carried, key=carried.count) if carried else None), False


def users_of(names: dict) -> dict:
    """{instruction: [the instructions that read its result]}."""
    users: dict = {}
    for instruction, entries in names.items():
        for read in entries[0][2]:
            users.setdefault(read, []).append(instruction)
    return users


def inherited(instruction: str, names: dict, users: dict, reach: int = 6) -> Optional[str]:
    """What a pathless instruction's result is for: the scope of the
    nearest operation that reads it (through further pathless ones and a
    shallow scope's) and is booked to a scope that is not shallow; else
    the shallow scope met on the way; else None."""
    seen, front, shallow = {instruction}, [instruction], None
    for _ in range(reach):
        following = []
        for name in front:
            for user in users.get(name, ()):
                if user in seen or names[user][0][0] in LOOKED_THROUGH:
                    continue
                seen.add(user)
                scope = book(names[user])[0]
                if scope is not None and scope not in SHALLOW:
                    return scope
                shallow = shallow or scope
                following.append(user)
        front = following
    return shallow


def step_table(run: dict) -> Optional[dict]:
    """{"busy_s", "scopes": {scope or "unscoped": {"seconds", "ops":
    {operation: seconds}}}, "fused_with_optim_s", "unknown": {operation:
    seconds}}: the traced window's operations, seconds the mean over
    devices. Made once a run (the record's one load of the compiled step
    is seconds) and kept on it."""
    if "step_table" in run:
        return run["step_table"]
    if not run.get("trace") or not run.get("busy"):
        return None
    from ray_tpu import obs

    record = getattr(obs, "op_names", None)
    names = record() if record is not None else None
    run["step_table"] = None if not names else table_of(
        run["trace"], run["win"], run["busy"]["busy_s"], names, run.get("rules"))
    return run["step_table"]


def self_times(events: list, win: tuple) -> list:
    """[(name, start, seconds)]: each event's time inside the window that
    no event it holds covers (a leaf: all of it)."""
    out, stack = [], []   # stack of [name, start, end, seconds inside, its children's]

    def close():
        name, start, _, mine, held = stack.pop()
        out.append((name, start, max(0.0, mine - held)))

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        # 1 ns of slack: an op that starts as its neighbour ends is no child of it
        while stack and s >= stack[-1][2] - 1e-9:
            close()
        inside = max(0.0, min(s + d, win[1]) - max(s, win[0]))
        if stack:
            stack[-1][4] += inside
        stack.append([name, s, s + d, inside, 0.0])
    while stack:
        close()
    return out


def table_of(trace: tr.Trace, win: tuple, busy_s: float, names: dict,
             rules: Optional[list] = None) -> dict:
    """`rules` (trace_names): which of the trace's programs is the train
    step; without them every operation is taken for the step's."""
    n_dev = max(len(trace.device_ops), 1)
    scopes: dict = {}
    unknown: dict = {}
    fused = 0.0
    booked: dict = {}   # operation -> (scope, fused with optim)
    users = users_of(names)
    for dev, evs in trace.device_ops.items():
        steps = sorted((s, s + d) for name, s, d in trace.device_programs.get(dev, ())
                       if rules and tr.classify(name, rules) == STEP_PROGRAM)
        for name, start, seconds in self_times(evs, win):
            seconds /= n_dev
            if seconds <= 0:
                continue
            entries = names.get(instruction_of(name))
            if steps and not any(a - 1e-9 <= start < b for a, b in steps):
                entries = None  # another program's operation, whatever it is called
            if entries is None:
                scope, with_optim = None, False
                unknown[name] = unknown.get(name, 0.0) + seconds
            else:
                if name not in booked:
                    booked[name] = book(entries)
                    if booked[name][0] is None and "/" not in entries[0][1]:
                        booked[name] = inherited(instruction_of(name), names, users), False
                scope, with_optim = booked[name]
            row = scopes.setdefault(scope or UNSCOPED, {"seconds": 0.0, "ops": {}})
            row["seconds"] += seconds
            row["ops"][name] = row["ops"].get(name, 0.0) + seconds
            if with_optim:
                fused += seconds
    return {"busy_s": busy_s, "scopes": scopes, "fused_with_optim_s": fused,
            "unknown": unknown}


def family_seconds(table: dict) -> dict:
    out: dict = {}
    for scope, row in table["scopes"].items():
        fam = family(scope)
        out[fam] = out.get(fam, 0.0) + row["seconds"]
    return out


def family_pct(run: dict, fam: str) -> Optional[float]:
    """Device time of one family, % of the traced window's busy time; 0.0
    where the record is there and nothing ran under the family."""
    table = step_table(run)
    if table is None:
        return None
    return 100.0 * family_seconds(table).get(fam, 0.0) / table["busy_s"]


def fused_with_optim_pct(run: dict) -> Optional[float]:
    table = step_table(run)
    return None if table is None else 100.0 * table["fused_with_optim_s"] / table["busy_s"]


def fallback_sites(run: dict) -> Optional[float]:
    """Call sites that took the plain path where an overlapped ring or a
    Pallas grouped matmul stands: the program's engage counters
    (step_scopes' `engage_counters`), counted while tracing, in this
    process. None where no site of either kind was counted at all, on
    either path: a step that has no such site, or a process whose counters
    were not recorded, is not "all engaged"."""
    if run.get("kind") != "train":
        return None
    from ray_tpu import obs

    counters = getattr(obs, "layer_counters", None)
    if counters is None:
        return None
    got = counters()

    def count(names):
        return sum(got[n]["count"] for n in names if n in got)

    kinds = [(count(k["engaged"]), count(k["fallback"]))
             for k in VOCABULARY["engage_counters"].values()]
    if not any(engaged or fallback for engaged, fallback in kinds):
        return None
    return float(sum(fallback for _, fallback in kinds))
