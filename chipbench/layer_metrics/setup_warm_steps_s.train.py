"""Train step: seconds of warm steps 1..3 and the block_until_ready that ends the warm-up.
None where the run carries no table of phases (chipbench/phases.py)."""

from chipbench import readers_setup


def read(run):
    return readers_setup.phase_s(run, "warm_steps")
