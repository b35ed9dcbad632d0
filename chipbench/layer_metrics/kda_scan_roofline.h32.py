"""Kernels: the traced steps' device time under `kda.scan` (Kimi-Linear's four KDA layers at ALL
32 heads of 128, forward and backward: ops/kda.py's two kernels a layer at a grid four times
Solar-Open2's) against max(operations / 197e12, bytes / 819e9) of the work NO implementation can
avoid (costs_kimi_linear.scan_cost: the position-by-position rule's products, q, k, v, beta and
the [T, H, dk] decay read and o written once) (%)."""

from chipbench import readers_kimi_linear


def read(run):
    return readers_kimi_linear.scan_roofline(run)
