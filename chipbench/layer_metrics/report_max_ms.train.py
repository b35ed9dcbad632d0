"""Trainer: the LONGEST `train.report` (inside session.report, on the worker) of the whole
window, in milliseconds, from the program's timeline: the tail beside `report_ms.train`'s
median of the three traced steps. None without a timeline or without a report in the window."""

from chipbench import readers_timeline


def read(run):
    return readers_timeline.report_max_ms(run)
