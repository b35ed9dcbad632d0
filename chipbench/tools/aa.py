#!/usr/bin/env python3
"""A/A: one tree as parent AND as change, as the driver's check sets two sides up.

    git add -A && git archive $(git write-tree) -o .scratch/aa/tree.tar      # here
    python -m chipbench.tools.aa --tar .scratch/aa/tree.tar --workload <cell> \\
        --per-side 12 [--seconds 10] [--seed0 n] [--fresh-b 1]                # on the chip

The archive is unpacked twice, side by side, under .scratch/aa/<cell>/{A,B}; each side
gets a HOME, XDG_CACHE_HOME and TMPDIR of its own and its own <checkout>/.jax_cache
(JAX_COMPILATION_CACHE_DIR is taken out of the children's environment). Every run is a
new process of the benchmark's own command, started from its side's checkout (this
parent never imports JAX). Each side's first run compiles and is set apart. Then the
warm runs in the order A B B A A B B A ..., so that each side goes first as often as
second; run k of either side has the same seed. With --fresh-b 1 side B is made afresh
before EVERY run (unpacked again: no __pycache__; its files pushed out of the page
cache), its compile cache kept.

Prints every run, then per metric and start-up phase: each side's median and quartile
distance, (B - A) / A of the medians, how often B read above A in a pair, the same
split by which side went first, and every two-pair median difference there is (the
driver judges an unclaimed cell on the median of two pairs) with the 95th percentile
of its absolute value. Everything is written to chiprun_out/chipbench/aa-<cell>.json.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import statistics
import sys
import tarfile

from chipbench import phases
from chipbench.tools import sets

KEPT = ".jax_cache"  # what a side's first run made and its later runs need


def unpack(tar: str, where: str, out_of_page_cache: bool = False) -> None:
    """The archive at `where`, anew; a compile cache that is there is kept."""
    kept = where + ".kept"
    if os.path.isdir(os.path.join(where, KEPT)):
        os.rename(os.path.join(where, KEPT), kept)
    shutil.rmtree(where, ignore_errors=True)
    os.makedirs(where)
    with tarfile.open(tar) as t:
        t.extractall(where, filter="data")
    if os.path.isdir(kept):
        os.rename(kept, os.path.join(where, KEPT))
    if out_of_page_cache:
        for d, dirs, files in os.walk(where):
            if KEPT in dirs:
                dirs.remove(KEPT)
            for fn in files:
                fd = os.open(os.path.join(d, fn), os.O_RDONLY)
                try:
                    os.fsync(fd)
                    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                finally:
                    os.close(fd)


def side_env(base: str, side: str) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "BENCH_RUN")}
    home = os.path.join(base, f"home_{side}")
    env.update(HOME=home, XDG_CACHE_HOME=os.path.join(home, ".cache"),
               TMPDIR=os.path.join(base, f"tmp_{side}"))
    for d in (env["XDG_CACHE_HOME"], env["TMPDIR"]):
        os.makedirs(d, exist_ok=True)
    return env


def one(command: list, cwd: str, env: dict, workload: str, seed: int, seconds: float) -> dict:
    """sets.one from a side's checkout in its environment, flattened: the result line's
    metrics and, from the run's `setup` line, every phase beside them."""
    got = sets.one(command, workload, seed, seconds, 0, cwd, env)
    rec = {"seed": seed, "rc": got["rc"], "wall_s": got["wall_s"]}
    if got["result"] is None:
        rec["stderr"] = got.get("stderr", "")[-2000:]
        return rec
    result = got["result"]
    rec.update(correct=result["correct"], memory_peak_bytes=result["device"]["memory_peak_bytes"],
               values={k: v["value"] for k, v in result["metrics"].items()})
    for ln in got["earlier"]:
        earlier = json.loads(ln) if ln.startswith("{") else {}
        if earlier.get("event") == "setup":
            # each phase beside setup_s, the runtime's spans, what none of them covers,
            # and the process's whole age (setup_s leaves the machine's phases out)
            table = earlier["phases"]
            machine = sum(table[k] for k in phases.MACHINE)
            rec["values"].update({f"phase.{k}": v for k, v in table.items()})
            rec["values"]["phase.runtime"] = earlier["runtime_s"] or 0.0
            rec["values"]["phase.machine"] = machine
            rec["values"]["phase.unnamed"] = (earlier["setup_s"] + machine - sum(table.values())
                                              - rec["values"]["phase.runtime"])
            rec["values"]["process_age_s"] = earlier["setup_s"] + machine
            rec["programs"] = earlier["programs"]
        # what explains a window that reads low: a stalled step, a compile inside it
        if earlier.get("event") == "train" and earlier["step_s"]:
            rec["slowest_step_s"] = max(earlier["step_s"])
        if earlier.get("event") == "window done":
            rec["compiles_in_window"] = earlier["compiles_in_window"]
    return rec


def quartile_distance(values: list) -> float | None:
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def compare(a: list, b: list, first: list) -> dict:
    """a[k], b[k]: the two sides' run k of one metric; first[k]: which side went first."""
    ma, mb = statistics.median(a), statistics.median(b)
    diffs = [y - x for x, y in zip(a, b)]
    by_first = {}
    for side in "AB":
        d = [v for v, f in zip(diffs, first) if f == side]
        by_first[f"{side}_first"] = {"n": len(d), "median_b_minus_a": statistics.median(d) if d else None,
                                     "b_above_a": sum(1 for v in d if v > 0)}
    draws = [(statistics.median([b[i], b[j]]) - statistics.median([a[i], a[j]]))
             / statistics.median([a[i], a[j]])
             for i, j in itertools.combinations(range(len(a)), 2)
             if statistics.median([a[i], a[j]])]
    out = {"n": len(a), "A": {"median": ma, "quartile_distance": quartile_distance(a)},
           "B": {"median": mb, "quartile_distance": quartile_distance(b)},
           "b_minus_a_over_a": (mb - ma) / ma if ma else None,
           "b_above_a": sum(1 for v in diffs if v > 0), "b_below_a": sum(1 for v in diffs if v < 0),
           "by_first": by_first}
    if draws:
        mag = sorted(abs(v) for v in draws)
        out["two_pair_draws"] = {
            "n": len(draws), "mean": statistics.fmean(draws), "worst": mag[-1],
            "p95_abs": mag[min(len(mag) - 1, int(0.95 * len(mag)))],
            "b_worse_by_over_10pct": sum(1 for v in draws if v > 0.10)}
    return out


def main() -> int:
    from chipbench import manifest as mf

    ap = argparse.ArgumentParser()
    ap.add_argument("--tar", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--per-side", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=3100000000)
    ap.add_argument("--fresh-b", type=int, default=0)
    args = ap.parse_args()
    root = mf.ROOT
    manifest = mf.load_manifest(root)
    seconds = args.seconds or manifest["run_seconds"]
    tag = args.workload + ("-fresh" if args.fresh_b else "")
    base = os.path.join(root, ".scratch", "aa", tag)
    shutil.rmtree(base, ignore_errors=True)
    tar = os.path.abspath(args.tar)
    sides = {s: os.path.join(base, s) for s in "AB"}
    envs = {s: side_env(base, s) for s in "AB"}
    for s in "AB":
        unpack(tar, sides[s])

    def run(side, seed, kind, k=None, went_first=None):
        if side == "B" and args.fresh_b:
            unpack(tar, sides["B"], out_of_page_cache=True)
        rec = one(manifest["command"], sides[side], envs[side], args.workload, seed, seconds)
        rec.update(side=side, kind=kind, k=k, went_first=went_first)
        print(json.dumps(rec), flush=True)
        return rec

    runs = [run(s, args.seed0 - 1, "first") for s in "AB"]
    for k in range(args.per_side):
        order = "AB" if k % 2 == 0 else "BA"
        for s in order:
            runs.append(run(s, args.seed0 + k, "warm", k, order[0]))
    warm = {s: sorted((r for r in runs if r["kind"] == "warm" and r["side"] == s
                       and "values" in r), key=lambda r: r["k"]) for s in "AB"}
    both = sorted({r["k"] for r in warm["A"]} & {r["k"] for r in warm["B"]})
    a = [r for r in warm["A"] if r["k"] in both]
    b = [r for r in warm["B"] if r["k"] in both]
    table = {}
    for name in sorted({n for r in a + b for n in r["values"]}):
        if all(name in r["values"] for r in a + b):
            table[name] = compare([r["values"][name] for r in a], [r["values"][name] for r in b],
                                  [r["went_first"] for r in a])
    summary = {"workload": args.workload, "fresh_b": args.fresh_b, "seconds": seconds,
               "per_side": args.per_side, "pairs_compared": len(both),
               "first_setup_s": {r["side"]: r.get("values", {}).get("setup_s")
                                 for r in runs if r["kind"] == "first"},
               "correct": [r.get("correct") for r in runs], "rcs": [r["rc"] for r in runs],
               "wall_s": [r["wall_s"] for r in runs], "metrics": table}
    print(json.dumps(summary, indent=1), flush=True)
    out = os.path.join(root, "chiprun_out", "chipbench")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"aa-{tag}.json"), "w") as f:
        json.dump({"summary": summary, "runs": runs}, f, indent=1)
    shutil.rmtree(base, ignore_errors=True)
    return 0 if all(r["rc"] == 0 and r.get("correct") for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
