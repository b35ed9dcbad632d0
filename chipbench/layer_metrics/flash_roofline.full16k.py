"""Kernels: time of the `attn.attend.N` flash kernels (the full layer's forward, dq and dk/dv
kernels at 16,384 keys: four kv blocks of 4,096, k and v fetched again for every q block) in the
traced steps against max(operations / 197e12, bytes / 819e9) of the causal pairs at 32 / 4 heads
of 128, five matmuls a pair in the backward where the two kernels run seven
(costs_mellum2.flash_cost) (%)."""

from chipbench import readers_mellum2


def read(run):
    return readers_mellum2.flash_roofline(run, "attn.attend")
