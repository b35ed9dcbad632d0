"""Machine: seconds of the first jax.devices(): the TPU client opens.
None where the run carries no table of phases (chipbench/phases.py)."""

from chipbench import readers_setup


def read(run):
    return readers_setup.phase_s(run, "backend")
