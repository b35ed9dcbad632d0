"""Kernels: time of the `attn.attend.N` flash kernels (Olmo-Hybrid's full-attention layers', 30
query and 30 key-value heads of 128, forward and backward) in the traced steps against
max(operations / 197e12, bytes / 819e9) of the causal pairs (costs_olmo_hybrid.flash_cost) (%)."""

from chipbench import readers_olmo_hybrid


def read(run):
    return readers_olmo_hybrid.flash_roofline(run)
