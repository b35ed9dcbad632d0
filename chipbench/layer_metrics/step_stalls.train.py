"""Trainer: steps of the WHOLE window, not of the three traced ones, whose period (one
`train.step` entry to the next) is over 1.2 x the window's median: `obs.slow_steps` on the
window's rows, the profiler's two pauses of a traced run taken off as the runner takes them
off its own clock (chipbench/readers_timeline.py). 0 is a value; None without a timeline."""

from chipbench import readers_timeline


def read(run):
    slow = readers_timeline.stalls(run)
    return None if slow is None else len(slow)
