"""Kernels: the traced steps' device time under `kda.scan` (every KDA layer, forward and
backward, whatever computes it: jax.numpy over chunks today) against max(operations / 197e12,
bytes / 819e9) of the work NO implementation can avoid (costs_solar_open2.scan_cost: the
position-by-position rule's products, q, k, v, beta and the [T, H, dk] decay read and o written
once) (%). A chunked form's extra products and a rematerialised forward lower it."""

from chipbench import readers_solar_open2


def read(run):
    return readers_solar_open2.scan_roofline(run)
