"""Device: 1 minus the union of op intervals over the traced window (%)."""

from chipbench import readers


def read(run):
    return readers.idle_pct(run)
