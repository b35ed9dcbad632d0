"""Kernels: time of all grouped-matmul kernels in the traced steps against the nine matmuls over
the (token, expert) pairs ACTUALLY routed to the held experts, ten pairs a token of which about
1 in 32 is held, every expert block (costs_laguna.grouped_matmul_cost at K 3072 / N 1024; the
step's `pairs_elsewhere`) (%)."""

from chipbench import readers_laguna


def read(run):
    return readers_laguna.expert_matmul_roofline_held(run)
