"""Runtime and trainer: CPU seconds of the process's threads OTHER than the loop's inside the
window (process CPU less the loop thread's, from the clocks the step's timeline takes at
every step entry: the in-process runtime's scheduler, actor and telemetry threads, XLA's
own), over the window's seconds, in %: 100 is one core busy all the time. None without a
timeline."""

from chipbench import readers_timeline


def read(run):
    return readers_timeline.other_cpu_pct(run)
