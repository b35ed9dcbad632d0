"""Kernels: flash forward + backward kernel time in the traced steps against
max(operations / 197e12, bytes / 819e9) from shapes (%); compute-bound at these sizes."""

from chipbench import readers


def read(run):
    return readers.flash_roofline(run)
