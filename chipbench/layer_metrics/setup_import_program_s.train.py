"""Runtime and trainer: seconds importing the program (`ray_tpu`, `ray_tpu.train`, the model
modules a builder's build() pulls in, the mesh modules) and loading the cell's plugins, with
jax, numpy and optax already in sys.modules: the sum of the import blocks of run.py and of
the runner, wherever they stand. What else the program imports (jax.experimental.pallas)
is the program's to shorten and counts here.
None where the run carries no table of phases (chipbench/phases.py)."""

from chipbench import readers_setup


def read(run):
    return readers_setup.phase_s(run, "import_program")
