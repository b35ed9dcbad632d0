"""Device: memory_stats() peak_bytes_in_use on the fullest chip (GiB). Undercounts a
program's temporaries (PR 21); memory_analysis() of the step is on an earlier line."""

from chipbench import readers


def read(run):
    return readers.hbm_peak_gib(run)
