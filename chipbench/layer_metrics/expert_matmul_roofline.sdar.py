"""Kernels: time of the expert layer's grouped-matmul kernels in the traced steps against
max(operations / 197e12, bytes / 819e9) of the (row, expert) pairs of BOTH copies that were
routed to the 16 held experts, at K 2048 / N 768 (costs_sdar.grouped_matmul_cost) (%)."""

from chipbench import readers_sdar


def read(run):
    return readers_sdar.expert_matmul_roofline(run)
