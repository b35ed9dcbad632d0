"""Train step: median milliseconds of the program's `train.step` span among the WINDOW's
steps, traced or not: the host's time inside the step's call (arguments flattened, the
program enqueued), from the program's own timeline (`obs.step_timeline`), which keeps every
step of every run. None where the program keeps no timeline (the parent of PR 51)."""

from chipbench import readers_timeline


def read(run):
    return readers_timeline.median_ms(run, "dispatch_s")
