#!/usr/bin/env python3
"""The HOST's side of a cell's steps, window by window: which steps were slow, and why.

    python -m chipbench.tools.step_timeline --workload <cell> --seed <n> [--seconds 10]
                                            [--trace 0|1] [--runs k]        # on the chip

Makes k runs of the cell through the benchmark's own entry (`chipbench.run`: a process a
run, seeds n, n + 1, ..., the same window, its lines and its result line printed as ever),
and after each keeps what the program itself recorded of the window's steps: the rows of
`ray_tpu.obs.step_timeline` inside the window (chipbench/readers_timeline.py: the readers'
own rows, so the tool and `step_stalls.train` cannot disagree) and `obs.slow_steps` of
them. Untraced windows are the point: the program keeps the timeline in every run.

Prints, after the runs, a row a window (steps, the median period and its four segments,
the slow steps, what they cost, what everything above the median cost, the collector's ms,
the other threads' CPU) and every slow
step with its segments, its clocks and its ONE cause. The same as JSON, every period kept,
in chiprun_out/chipbench/step_timeline-<cell>-s<seed>-t<trace>.json (one run) and
step_timeline-<cell>-s<seed>-t<trace>-x<k>.json (k runs). A traced run adds the device's
side: busy ms a step, `step_gap_ms.train`, `step_gap_program_pct.train`, the idle share.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

from chipbench import manifest as mf, readers_timeline as rt  # both stay off JAX

SEGMENTS = rt.SEGMENTS


def _ms(seconds):
    return None if seconds is None else 1e3 * seconds


def over_median_pct(rows: list) -> float:
    """The sum over the window's steps of what each period has above the median period,
    over the window's seconds, in %."""
    periods = [r["period_s"] for r in rows if r["period_s"] is not None]
    if not periods:
        return 0.0
    median = statistics.median(periods)
    return 100.0 * sum(p - median for p in periods if p > median) / sum(periods)


def summary(run: dict, workload: str, seed: int, trace: int) -> dict:
    """One window, from the runner's `run` and the program's timeline; {"error"} where the
    program keeps none."""
    head = {"workload": workload, "seed": seed, "trace": trace}
    rows = rt.window_rows(run)
    if rows is None:
        return {**head, "error": "the program keeps no timeline of its steps"}
    slow = rt.stalls(run)
    w0 = run["window_wall"][0]
    out = {
        **head, "steps": len(rows), "values": run["values"],
        "median_period_ms": rt.median_ms(run, "period_s"),
        "median_ms": {seg: rt.median_ms(run, seg) for seg in SEGMENTS},
        "median_thread_cpu_ms": rt.median_ms(run, "thread_cpu_s"),
        "step_stalls": len(slow), "stall_loss_pct": rt.stall_loss_pct(run),
        # everything above the median, slow by the factor or not: nineteen steps 12 ms late
        # cost what two stalled steps do and are no `step_stalls`
        "over_median_pct": over_median_pct(rows),
        "gc_pause_ms": rt.gc_pause_ms(run), "other_cpu_pct": rt.other_cpu_pct(run),
        "nivcsw": sum(r["nivcsw"] or 0 for r in rows),
        "report_max_ms": rt.report_max_ms(run),
        "compile_s": sum(r["compile_s"] for r in rows),
        "periods_ms": [_ms(r["period_s"]) for r in rows],
        # every step's segments and clocks, ms: a run whose MANY steps are a little slow
        # holds no stall over the factor, and this is where it shows
        "steps_ms": [[_ms(r[key]) for key in SEGMENTS + ("thread_cpu_s", "other_cpu_s", "gc_s")]
                     for r in rows],
    }
    by_start = {r["start"]: k for k, r in enumerate(rows)}
    out["slow"] = [{
        "step": by_start[s["start"]], "at_s": s["start"] - w0, "period_ms": _ms(s["period_s"]),
        "excess_ms": _ms(s["excess_s"]), "cause": s["cause"], "segment": s["segment"],
        **{seg[:-2] + "_ms": _ms(s[seg]) for seg in SEGMENTS},
        "thread_cpu_ms": _ms(s["thread_cpu_s"]), "other_cpu_ms": _ms(s["other_cpu_s"]),
        "nivcsw": s["nivcsw"], "gc_ms": _ms(s["gc_s"]), "gc_generation": s["gc_generation"],
        "compile_s": s["compile_s"],
    } for s in slow]
    if run.get("busy"):
        busy = run["busy"]
        out["device"] = {
            "busy_ms_a_step": 1e3 * busy["busy_s"] / run["traced_steps"],
            "idle_pct": 100.0 * (1.0 - busy["busy_s"] / busy["window_s"]),
            "step_gap_ms": rt.step_gap_ms(run),
            "step_gap_program_pct": rt.step_gap_program_pct(run),
        }
    return out


def _fmt(value, spec=".2f") -> str:
    return "-" if value is None else format(value, spec)


def render(windows: list) -> str:
    """The table of `summary` windows: a row a window, then every slow step."""
    out = [f"{'cell':20s} {'seed':>11s} t {'steps':>5s} {'period':>9s} {'dispatch':>8s} "
           f"{'wait':>9s} {'report':>7s} {'between':>7s} {'stalls':>6s} {'loss%':>6s} "
           f"{'gc ms':>7s} {'other%':>6s}  (medians, ms)"]
    for w in windows:
        if "error" in w:
            out.append(f"{w['workload']:20s} {w['seed']:11d} {w['trace']} {w['error']}")
            continue
        m = w["median_ms"]
        out.append(
            f"{w['workload']:20s} {w['seed']:11d} {w['trace']} {w['steps']:5d} "
            f"{_fmt(w['median_period_ms'], '9.3f')} {_fmt(m['dispatch_s'], '8.3f')} "
            f"{_fmt(m['wait_s'], '9.3f')} {_fmt(m['report_s'], '7.3f')} "
            f"{_fmt(m['between_s'], '7.3f')} {w['step_stalls']:6d} "
            f"{_fmt(w['stall_loss_pct'], '6.3f')} {_fmt(w.get('over_median_pct'), '6.3f')} "
            f"{_fmt(w['gc_pause_ms'], '7.2f')} "
            f"{_fmt(w['other_cpu_pct'], '6.1f')}")
        if "device" in w:
            d = w["device"]
            out.append(f"{'':20s} device: busy {_fmt(d['busy_ms_a_step'], '.3f')} ms a step, "
                       f"idle {_fmt(d['idle_pct'], '.3f')}%, step gap "
                       f"{_fmt(d['step_gap_ms'], '.3f')} ms of which the program's spans "
                       f"{_fmt(d['step_gap_program_pct'], '.1f')}%")
    slow = [(w, s) for w in windows for s in w.get("slow", ())]
    held = sum(1 for w in windows if w.get("slow"))
    out.append(f"{len(windows)} window(s), {held} held a slow step, {len(slow)} slow step(s)")
    for w, s in slow:
        out.append(
            f"  slow {w['workload']} seed {w['seed']} step {s['step']} at {s['at_s']:.2f} s: "
            f"period {s['period_ms']:.1f} ms (+{s['excess_ms']:.1f}), cause {s['cause']}, grew in "
            f"{s['segment'][:-2]}: dispatch {_fmt(s['dispatch_ms'])} wait {_fmt(s['wait_ms'])} "
            f"report {_fmt(s['report_ms'])} between {_fmt(s['between_ms'])}; thread cpu "
            f"{_fmt(s['thread_cpu_ms'])} other cpu {_fmt(s['other_cpu_ms'])} nivcsw "
            f"{_fmt(s['nivcsw'], 'd')} gc {_fmt(s['gc_ms'])} (generation "
            f"{_fmt(s['gc_generation'], 'd')}) compile {s['compile_s']:.3f} s")
    causes: dict = {}
    for _, s in slow:
        causes[s["cause"]] = causes.get(s["cause"], 0) + 1
    if causes:
        out.append("causes: " + ", ".join(f"{c} {n}" for c, n in sorted(causes.items())))
    return "\n".join(out)


def out_path(workload: str, seed: int, trace: int, runs: int = 1) -> str:
    many = f"-x{runs}" if runs > 1 else ""
    return os.path.join(mf.ROOT, "chiprun_out", "chipbench",
                        f"step_timeline-{workload}-s{seed}-t{trace}{many}.json")


def write(path: str, data) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f)


def one_run(args) -> int:
    """This process IS the run: the benchmark's own entry, its runner's `run` kept."""
    from chipbench import run as harness

    runs: list = []
    load_plugin = mf.load_plugin

    def keeping(root, kind, name):
        mod = load_plugin(root, kind, name)
        if kind == "runners":
            run = mod.run

            def kept(ctx):
                runs.append(run(ctx))
                return runs[-1]

            mod.run = kept
        return mod

    mf.load_plugin = keeping
    try:
        code = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                             "--seconds", str(args.seconds), "--trace", str(args.trace)])
    finally:
        mf.load_plugin = load_plugin
    if code or not runs:
        print("no timeline: the run failed", file=sys.stderr)
        return code or 1
    window = summary(runs[-1], args.workload, args.seed, args.trace)
    write(out_path(args.workload, args.seed, args.trace), window)
    print(render([window]), flush=True)
    return 1 if "error" in window else 0


def many_runs(args) -> int:
    """A process a run (a chip belongs to one process at a time; this one stays off JAX)."""
    windows, worst = [], 0
    for j in range(args.runs):
        seed = args.seed + j
        code = subprocess.call(
            [sys.executable, "-m", "chipbench.tools.step_timeline", "--workload",
             args.workload, "--seed", str(seed), "--seconds", str(args.seconds),
             "--trace", str(args.trace)], cwd=mf.ROOT)
        worst = worst or code
        try:
            with open(out_path(args.workload, seed, args.trace)) as f:
                windows.append(json.load(f))
        except (OSError, ValueError):
            windows.append({"workload": args.workload, "seed": seed, "trace": args.trace,
                            "error": f"the run exited {code} and left no timeline"})
    write(out_path(args.workload, args.seed, args.trace, args.runs), windows)
    print(render(windows), flush=True)
    return worst


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--runs", type=int, default=1)
    args = ap.parse_args(argv)
    if args.runs < 1:
        ap.error("--runs is at least 1")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    return one_run(args) if args.runs == 1 else many_runs(args)


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
