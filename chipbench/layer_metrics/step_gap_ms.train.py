"""Device: median milliseconds, among the traced steps, from the last operation inside one
train-step program on the first device to the first operation inside the next: the gap the
host leaves between two steps (the batch maker's own program runs inside it). The trace's
alone; None without a trace or with fewer than two train-step programs in it."""

from chipbench import readers_timeline


def read(run):
    return readers_timeline.step_gap_ms(run)
