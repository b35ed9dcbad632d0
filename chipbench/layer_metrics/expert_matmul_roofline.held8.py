"""Kernels: time of all grouped-matmul kernels in the traced steps against the nine matmuls over
the (token, expert) pairs ACTUALLY routed to the held experts, eight pairs a token of which about
1 in 8 is held, every layer (costs_keye.grouped_matmul_cost at K 2048 / N 768, 16 held; the
step's `pairs_elsewhere`) (%)."""

from chipbench import readers_keye


def read(run):
    return readers_keye.expert_matmul_roofline_held(run)
