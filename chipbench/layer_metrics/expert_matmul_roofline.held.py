"""Kernels: grouped-matmul kernel time in the traced steps against max(operations / 197e12,
bytes / 819e9) of the nine matmuls over the pairs ACTUALLY routed to the experts held here
(median of the traced steps' statistics; costs_zaya), from shapes (%). Pairs routed to experts
another chip holds are rows of no matmul."""

from chipbench import readers_zaya


def read(run):
    return readers_zaya.expert_matmul_roofline_held(run)
