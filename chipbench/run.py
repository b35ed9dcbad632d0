#!/usr/bin/env python3
"""One run of one cell of the benchmark on the chip.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, one new process per run. Opens the chip
first and exits non-zero unless JAX reports a TPU with exactly the
cell's number of chips (JAX_PLATFORMS is never set here, and nothing
falls back). Weights and inputs come from --seed. Warm-up of this
cell's shapes counts as set-up; then the window of --seconds; then the
correctness check. Every stretch of the start-up is timed by name
(chipbench/phases.py); setup_s is the process's age at the window less
the machine's phases (interpreter, third-party imports, the TPU
client's opening). Earlier lines are free text (each names the
device; the `setup` line has every phase and each program compiled or
loaded); the LAST line of stdout is the one JSON object of the
contract. --trace 0 reports the cell's end-to-end metrics, --trace 1
its per-layer metrics (read from a short profiler trace, the
program's counters and its spans) plus device busy/idle and a
breakdown.

Nothing here names a cell: the runner, the model builder, the traffic
generator and each per-layer reader are files found by name
(chipbench/manifest.py).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

from chipbench import phases

NO_TPU_EXIT = 3


def log(device: dict, **fields) -> None:
    """An earlier line: free-form, but it always names the device."""
    print(json.dumps({"device": device, **fields}, default=str), flush=True)


class CompileCounter:
    """Wall-clock times of every backend compile JAX makes in this
    process (a program loaded from the persistent cache counts too: the
    window should see neither)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.events.append((time.time(), duration))

    def between(self, a: float, b: float) -> int:
        return sum(1 for t, _ in self.events if a <= t <= b)


def open_chip(chips: int, what: str):
    """The chip first: cache placement, then the backend, then nothing
    but a TPU with exactly `chips` chips (exit NO_TPU_EXIT otherwise).
    -> (cache directory, CompileCounter, device record)."""
    import jax

    with phases.phase("import_program"):
        from ray_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    # every program, however quick to compile, comes from the cache in
    # the second run of a checkout (JAX's default skips compiles under 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()
    with phases.phase("backend"):
        devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if devs[0].platform != "tpu" or len(devs) != chips:
        print(f"{what} needs {chips} TPU chip(s); JAX found {device}", file=sys.stderr)
        sys.exit(NO_TPU_EXIT)
    return cache_dir, compiles, device


def main(argv=None) -> int:
    phases.since_process_start("interp")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from chipbench import manifest as mf  # the standard library only

    root = mf.ROOT
    manifest = mf.load_manifest(root)
    # what cannot run fails here, before seconds of imports: a cell that is not in the
    # manifest, a directory that holds the benchmark and no program
    mf.by_name(manifest["workloads"], args.workload, "workload")
    if importlib.util.find_spec("ray_tpu") is None:
        print(f"no program to measure: ray_tpu is not importable from {root}", file=sys.stderr)
        return 1
    with phases.phase("import"):
        # before any import of the program or of a plugin, so that none of those pays for them
        import jax  # noqa: F401
        import numpy  # noqa: F401
        import optax  # noqa: F401

    from chipbench import costs, readers_setup

    bad = mf.problems(manifest, root)
    if bad:
        print("BENCHMARK.json is not well-formed:\n  " + "\n  ".join(bad), file=sys.stderr)
        return 2
    cell = mf.load_cell(root, manifest, args.workload)

    cache_dir, compiles, device = open_chip(cell["chips"], args.workload)
    peaks = costs.load_peaks(device["kind"])
    out_dir = os.path.join(root, "chiprun_out", "chipbench",
                           f"{args.workload}-s{args.seed}-t{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    ctx = {
        "root": root, "manifest": manifest, "args": args, "device": device,
        "peaks": peaks, "out_dir": out_dir, "compiles": compiles,
        "log": lambda **f: log(device, **f),
        "names": mf.trace_names(root), **cell,
    }
    log(device, event="chip open", cache_dir=cache_dir, workload=args.workload,
        seed=args.seed, seconds=args.seconds, trace=args.trace,
        age_s=round(phases.process_age_s(), 2))
    with phases.phase("import_program"):
        runner = mf.load_plugin(root, "runners", cell["config"]["runner"])
    run = runner.run(ctx)
    run["compiles_in_window"] = compiles.between(*run["window_wall"])
    # every run says where its start-up went (a --trace 0 run reports no per-layer metric)
    log(device, event="setup", setup_s=run["values"]["setup_s"], phases=run["setup_phases"],
        runtime_s=readers_setup.layer_busy_s(readers_setup.RUNTIME_SPANS),
        programs=[(name, round(seconds, 3), how) for _, name, seconds, how
                  in readers_setup.compiles_before_window(run) or ()])
    log(device, event="window done", attempted=run["attempted"], failed=run["failed"],
        correct=run["correct"], checks=run["checks"], values=run["values"],
        compiles_total=len(compiles.events),
        compiles_in_window=run["compiles_in_window"])
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in mf.metrics_of(manifest, section, args.workload):
        if args.trace:
            value = mf.load_plugin(root, "layer_metrics", m["name"]).read(run)
        else:
            value = run["values"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = {**device, "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": dev}
    if args.trace:
        if run.get("busy") is None:
            print("the traced run read no operation on the device", file=sys.stderr)
            return 1
        dev["busy_s"] = run["busy"]["busy_s"]
        dev["window_s"] = run["busy"]["window_s"]
        result["breakdown"] = run["breakdown"]
    print(json.dumps(result), flush=True)
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
    # hard exit: the runtime's daemon threads race interpreter
    # finalization and can abort a run whose result is already printed
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
