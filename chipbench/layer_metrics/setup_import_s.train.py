"""Machine: seconds importing the third-party packages (jax, numpy, optax), made first in
main() so that no later import of the program or of a plugin pays for them.
None where the run carries no table of phases (chipbench/phases.py)."""

from chipbench import readers_setup


def read(run):
    return readers_setup.phase_s(run, "import")
