"""Kernels: time of the `mla.attend.N` flash kernels (Kimi-Linear's MLA layer: 32 heads, keys of
128 + 64 = 192, values of 128, no rotary, 8,192 keys in ONE kv block, forward and fused backward)
in the traced steps against max(operations / 197e12, bytes / 819e9) of the causal pairs
(costs_kimi_linear.flash_cost: 2 x (192 + 128) a pair and head forward, 2.5 times that backward,
q, k, v, o read and written once: the same work whatever implements it) (%)."""

from chipbench import readers_kimi_linear


def read(run):
    return readers_kimi_linear.flash_roofline(run)
