"""Kernels: time of the `attn.attend.N` flash kernels (Nemotron-H's attention layers', 32 query
heads over 2 key-value heads of 128, 8,192 keys, forward and backward) in the traced steps against
max(operations / 197e12, bytes / 819e9) of the causal pairs (costs_nemotron_h.flash_cost) (%)."""

from chipbench import readers_nemotron_h


def read(run):
    return readers_nemotron_h.flash_roofline(run)
