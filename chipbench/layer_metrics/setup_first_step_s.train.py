"""Model programs: seconds of warm step 0: the step's load from the persistent cache or its
compile, the batch maker's, and the first run (its step_s is in the warm report too).
None where the run carries no table of phases (chipbench/phases.py)."""

from chipbench import readers_setup


def read(run):
    return readers_setup.phase_s(run, "first_step")
