"""Kernels: the traced steps' device time under `ssm.scan` (every Mamba layer, forward and
backward, whatever computes it: einsums over chunks today) against max(operations / 197e12,
bytes / 819e9) of the work NO implementation can avoid (costs_nemotron_h.scan_cost: the
position-by-position scan's products, x, B, C, dt read and y written once) (%); memory-bound at
these sizes. A chunked form's extra products and a rematerialised forward lower it."""

from chipbench import readers_nemotron_h


def read(run):
    return readers_nemotron_h.scan_roofline(run)
