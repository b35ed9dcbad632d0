"""Train step: share of the traced steps' device time in leaf operations under NO listed scope of the
model, an operation the compiled step's text does not know among them (%): what the step's table
cannot explain. None without a trace or the program's record."""

from chipbench import readers_step


def read(run):
    return readers_step.family_pct(run, readers_step.UNSCOPED)
