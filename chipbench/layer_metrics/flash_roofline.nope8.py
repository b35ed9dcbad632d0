"""Kernels: time of the `attn.attend.N` flash kernels (Solar-Open2's GQA layer at the share's 8
query heads over 1 key-value head of 128, no rotary, 8,192 keys, forward and backward) in the
traced steps against max(operations / 197e12, bytes / 819e9) of the causal pairs
(costs_solar_open2.flash_cost) (%)."""

from chipbench import readers_solar_open2


def read(run):
    return readers_solar_open2.flash_roofline(run)
