"""Model programs: seconds from `jax.jit(init)(key)` (under a mesh `init_sharded_params`)
through `TrainState.create` until block_until_ready on the state: the init program's load
from the cache or compile, its run, and the optimizer state.
None where the run carries no table of phases (chipbench/phases.py)."""

from chipbench import readers_setup


def read(run):
    return readers_setup.phase_s(run, "init_params")
