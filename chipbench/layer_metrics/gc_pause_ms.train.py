"""Runtime and trainer: milliseconds Python's collector ran inside the window, every
generation: the growth of the program's `host.gc` counter (a `gc.callbacks` hook the runtime
installs) from step entry to step entry, as the step's timeline carries it. None without a
timeline."""

from chipbench import readers_timeline


def read(run):
    return readers_timeline.gc_pause_ms(run)
