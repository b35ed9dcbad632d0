"""Trainer: of the gaps `step_gap_ms.train` reads, the share of their seconds that the
PROGRAM's own host events `train.step` + `train.report` cover (the profiler's clock on both
sides): what the program could give back of the gap; the rest is the caller's wait for the
loss and its batch. None without a trace, or where the trace holds no `train.step` event
(the parent of PR 51)."""

from chipbench import readers_timeline


def read(run):
    return readers_timeline.step_gap_program_pct(run)
