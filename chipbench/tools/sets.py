#!/usr/bin/env python3
"""Sets of runs of one cell, as the bounds are set from (one call on the chip):

    python -m chipbench.tools.sets --workload <cell> --seeds 11,12,13 --sets 2 [--traced 1]

Every run is a new process of the benchmark's own command (this parent
never imports JAX: a chip belongs to one process at a time). Each set
uses the same seeds. Prints every run's last line, then for each
metric the median and the quartile spread (statistics.quantiles, n=4,
as a share of the median) of each set, the wider of the spreads, and
five times it. With --traced 1 one more run with --trace 1 follows.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def one(command: list, workload: str, seed: int, seconds: int, trace: int, root: str,
        env: dict | None = None) -> dict:
    """One run of the benchmark's command from the checkout `root` (in `env`, where given)."""
    t = time.monotonic()
    p = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(trace)], cwd=root, env=env, capture_output=True, text=True)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    rec = {"seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": round(time.monotonic() - t, 1), "result": None}
    if p.returncode == 0 and lines:
        rec["result"] = json.loads(lines[-1])
    else:
        rec["stderr"] = p.stderr[-3000:]
    rec["earlier"] = lines[:-1][-12:]
    return rec


def main() -> int:
    from chipbench import manifest as mf, stats

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--traced", type=int, default=0)
    args = ap.parse_args()
    root = mf.ROOT
    manifest = mf.load_manifest(root)
    seconds = args.seconds or manifest["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = []
    for k in range(args.sets):
        for seed in seeds:
            rec = one(manifest["command"], args.workload, seed, seconds, 0, root)
            rec["set"] = k
            runs.append(rec)
            print(json.dumps({k2: v for k2, v in rec.items() if k2 != "earlier"}), flush=True)
    traced = []
    for _ in range(args.traced):
        rec = one(manifest["command"], args.workload, seeds[0], seconds, 1, root)
        traced.append(rec)
        print(json.dumps(rec), flush=True)
    table = {}
    names = sorted({m for r in runs if r["result"] for m in r["result"]["metrics"]})
    for m in names:
        per_set = []
        for k in range(args.sets):
            vals = [r["result"]["metrics"][m]["value"] for r in runs
                    if r["set"] == k and r["result"] and m in r["result"]["metrics"]]
            # the first run of the first set compiles: its set-up is apart
            if m == "setup_s" and k == 0:
                vals = vals[1:]
            per_set.append({"n": len(vals), "median": statistics.median(vals) if vals else None,
                            "spread": stats.quartile_spread(vals), "values": vals})
        spreads = [s["spread"] for s in per_set if s["spread"] is not None]
        table[m] = {"sets": per_set, "widest_spread": max(spreads) if spreads else None,
                    "five_times": 5 * max(spreads) if spreads else None}
    summary = {"workload": args.workload, "seconds": seconds, "seeds": seeds,
               "correct": [r["result"]["correct"] if r["result"] else None for r in runs],
               "failed": [r["result"]["failed"] if r["result"] else None for r in runs],
               "attempted": [r["result"]["attempted"] if r["result"] else None for r in runs],
               "memory_peak_bytes": [r["result"]["device"]["memory_peak_bytes"]
                                     if r["result"] else None for r in runs],
               "wall_s": [r["wall_s"] for r in runs], "metrics": table}
    print(json.dumps(summary, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sets-{args.workload}.json"), "w") as f:
        json.dump({"summary": summary, "runs": runs, "traced": traced}, f, indent=1)
    return 0 if all(r["rc"] == 0 for r in runs + traced) else 1


if __name__ == "__main__":
    sys.exit(main())
