#!/usr/bin/env python3
"""The step's table of one traced run: where a train step's device time goes, by
the model's own names.

    python -m chipbench.tools.step_table --workload <cell> --seed <n> [--seconds 10]   # on the chip

Makes ONE run of the cell through the benchmark's own entry (`chipbench.run`, `--trace
1`: the same window, the same trace, the same readers, its lines and its result line
printed as ever), keeps the table the readers share (`readers_step.step_table`) and
prints it after the result line: a row a scope (ms a step on a device, share of the
busy time, its three largest operations by XLA's name, each `fwd` or `bwd` by its
path), the families, the operations under no scope by name, what is fused with the
optimizer's update, the compiled program's memory (the run's `memory_analysis`) and the engage
counters. The
same as JSON, with every operation, in chiprun_out/chipbench/step_table-<cell>-s<seed>.json.
The attribution rule is readers_step.py's docstring.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from chipbench import readers_step as rs

GIB = 2 ** 30


def direction(entries: list) -> str:
    """`bwd` where the operation (for a fusion: the matmul it holds, else any of its
    instructions) runs under the transposed program, else `fwd`."""
    inner = [e[1] for e in entries[1:] if e[0] in rs.MATMULS] or [e[1] for e in entries]
    return "bwd" if any("transpose(" in p for p in inner) else "fwd"


def _ms(seconds: float, steps: int) -> float:
    return 1e3 * seconds / steps


def memory_of(run: dict):
    """{"argument", "output", "temp", "alias"} bytes of the compiled step, from the
    traced run's `memory_analysis`; None where the runner could not take it."""
    m = run.get("memory_analysis") or {}
    if "temp_size_in_bytes" not in m:
        return None
    return {k: m[f"{k}_size_in_bytes"] for k in ("argument", "output", "temp", "alias")}


def render(table: dict, names: dict, memory, steps: int, title: str, top: int = 3,
           unscoped: int = 24, counters=None) -> str:
    busy = table["busy_s"]
    out = [f"step's table: {title}: {steps} traced step(s), busy {_ms(busy, steps):.3f} ms a "
           f"step on a device", f"{'scope':14s} {'ms/step':>9s} {'share%':>7s}  largest operations"]

    def named(op: str) -> str:
        entries = None if op in table["unknown"] else names.get(rs.instruction_of(op))
        return f"{op} {direction(entries)}" if entries else op

    rows = sorted(table["scopes"].items(), key=lambda kv: -kv[1]["seconds"])
    for scope, row in rows:
        ops = sorted(row["ops"].items(), key=lambda kv: -kv[1])[:top]
        out.append(f"{scope:14s} {_ms(row['seconds'], steps):9.3f} "
                   f"{100 * row['seconds'] / busy:7.2f}  "
                   + ", ".join(f"{named(op)} {_ms(s, steps):.3f}" for op, s in ops))
    families = sorted(rs.family_seconds(table).items(), key=lambda kv: -kv[1])
    total = sum(s for _, s in families)
    for fam, s in families:
        out.append(f"family {fam:10s} {_ms(s, steps):9.3f} {100 * s / busy:7.2f}")
    out.append(f"families and unscoped together {100 * total / busy:.2f}% of the busy time")
    out.append(f"fused with optim (a model's matmul carrying the update) "
               f"{_ms(table['fused_with_optim_s'], steps):.3f} ms, "
               f"{100 * table['fused_with_optim_s'] / busy:.2f}%")
    rest = table["scopes"].get(rs.UNSCOPED, {"ops": {}})["ops"]
    for op, s in sorted(rest.items(), key=lambda kv: -kv[1])[:unscoped]:
        why = "not the step's: no such instruction, or another program's" \
            if op in table["unknown"] else (
                names[rs.instruction_of(op)][0][1]
                or "no op_name, and nothing with one reads it")
        out.append(f"unscoped {op:44s} {_ms(s, steps):9.3f}  {why}")
    if memory:
        need = memory["argument"] + memory["output"] + memory["temp"] - memory["alias"]
        out.append("memory of the compiled step, GiB a device: "
                   + ", ".join(f"{k} {memory[k] / GIB:.3f}" for k in
                               ("argument", "output", "temp", "alias"))
                   + f"; arguments + outputs + temporaries - aliased {need / GIB:.3f} GiB")
    for kind, sides in rs.VOCABULARY["engage_counters"].items():
        got = {side: sum((counters or {}).get(n, {"count": 0})["count"] for n in sides[side])
               for side in ("engaged", "fallback")}
        out.append(f"sites {kind}: engaged {got['engaged']}, fallback {got['fallback']}")
    return "\n".join(out)


def as_json(table: dict, memory, steps: int) -> dict:
    """The table in ms a step, every operation kept."""
    return {
        "steps": steps, "busy_ms": _ms(table["busy_s"], steps),
        "scopes": {scope: {"ms": _ms(row["seconds"], steps),
                           "ops": {op: _ms(s, steps) for op, s in row["ops"].items()}}
                   for scope, row in table["scopes"].items()},
        "families": {f: _ms(s, steps) for f, s in rs.family_seconds(table).items()},
        "fused_with_optim_ms": _ms(table["fused_with_optim_s"], steps),
        "unknown": {op: _ms(s, steps) for op, s in table["unknown"].items()},
        "memory": memory,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from chipbench import manifest as mf, run as harness

    # the run the benchmark's own readers are handed, kept as it goes by
    runs: list = []
    step_table = rs.step_table

    def keeping(run):
        runs.append(run)
        return step_table(run)

    rs.step_table = keeping
    try:
        code = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                             "--seconds", str(args.seconds), "--trace", "1"])
    finally:
        rs.step_table = step_table
    table = runs[-1].get("step_table") if runs else None
    if code or table is None:
        print("no table: the run failed, or the program keeps no record of its step",
              file=sys.stderr)
        return code or 1
    from ray_tpu import obs

    names, memory, steps = obs.op_names(), memory_of(runs[-1]), runs[-1]["traced_steps"]
    print(render(table, names, memory, steps, f"{args.workload} seed {args.seed}",
                 counters=obs.layer_counters()), flush=True)
    out = os.path.join(mf.ROOT, "chiprun_out", "chipbench",
                       f"step_table-{args.workload}-s{args.seed}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(as_json(table, memory, steps), f)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:  # noqa: BLE001 - reported, then the hard exit below
        import traceback

        traceback.print_exc()
        code = 1
    # hard exit, as chipbench.run's own: the runtime's daemon threads race finalization
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
