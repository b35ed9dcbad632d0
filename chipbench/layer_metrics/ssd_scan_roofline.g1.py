"""Kernels: the traced steps' device time under `ssm.scan` (every Mamba layer of ONE group of 64
heads, forward, the forward run again under remat and backward) against max(operations / 197e12,
bytes / 819e9) of the work NO implementation can avoid, documents or not
(costs_granite_hybrid.scan_cost: the position-by-position scan's products, x, B, C, dt read and y
written once) (%). A chunked form's extra products, its masks and a rematerialised forward lower it."""

from chipbench import readers_granite_hybrid


def read(run):
    return readers_granite_hybrid.scan_roofline(run)
