"""The seconds of a start-up, named by phase, on the clock setup_s is read from.

setup_s is setup_s() read where the window opens: the process's age (the kernel's
clock) less the MACHINE's phases, which no change to the program shortens and which
carried 94-97% of the variance of the whole age in PR 31's A/A runs (PERF.md,
section 2). run.py and both training runners put the same clock round each stretch
of the way there:

    with phases.phase("backend"):
        devs = jax.devices()

and every stretch of one name adds up (the program's imports stand in four places).
The worker is a thread of this process, so one table serves all of them; the runner
hands a copy of it out in its `done` report, beside setup_s, and each per-layer
reader chipbench/layer_metrics/setup_<phase>_s.train.py takes its own name from it.
What no phase covers is setup_unnamed_s.train, by subtraction.
"""

from __future__ import annotations

import contextlib
import os

# the interpreter's start, the third-party imports (jax, numpy, optax), the TPU client
MACHINE = ("interp", "import", "backend")
# the named phases inside setup_s's clock; what it holds beside them and the runtime's
# spans is setup_unnamed_s.train
PROGRAM = ("import_program", "init_params", "first_step", "warm_steps")
_SECONDS: dict = {}


def process_age_s() -> float:
    """Seconds since this process was started (the kernel's clock; both files
    count in hundredths of a second)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def since_process_start(name: str) -> None:
    """Everything so far belongs to `name` (the interpreter, before main())."""
    _SECONDS[name] = _SECONDS.get(name, 0.0) + process_age_s()


@contextlib.contextmanager
def phase(name: str):
    a = process_age_s()
    try:
        yield
    finally:
        _SECONDS[name] = _SECONDS.get(name, 0.0) + process_age_s() - a


def setup_s() -> float:
    """The process's age less the machine's phases: the program's imports and the
    harness's own work count from the first line of main(), wherever they stand."""
    return process_age_s() - sum(_SECONDS.get(name, 0.0) for name in MACHINE)


def seconds() -> dict:
    """A copy of the table: {phase: seconds so far}."""
    return dict(_SECONDS)
