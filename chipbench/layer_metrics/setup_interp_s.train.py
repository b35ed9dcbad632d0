"""Machine: seconds from the start of the process (the kernel's clock) to the first line of
chipbench.run.main: the interpreter, `site`, and run.py's own standard-library imports.
None where the run carries no table of phases (chipbench/phases.py)."""

from chipbench import readers_setup


def read(run):
    return readers_setup.phase_s(run, "interp")
