"""Kernels: the traced steps' device time under `gdn.scan` (every linear layer, forward and
backward, whatever computes it: einsums over chunks today) against max(operations / 197e12,
bytes / 819e9) of the work NO implementation can avoid (costs_olmo_hybrid.scan_cost: the
position-by-position rule's products, q, k, v, g, beta read and o written once) (%); memory-bound
at these sizes. A chunked form's extra products and a rematerialised forward lower it."""

from chipbench import readers_olmo_hybrid


def read(run):
    return readers_olmo_hybrid.scan_roofline(run)
