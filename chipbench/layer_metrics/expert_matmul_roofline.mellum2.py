"""Kernels: time of all grouped-matmul kernels in the traced steps against the nine matmuls over
the (token, expert) pairs ACTUALLY routed to the held experts, eight pairs a token of which about
1 in 8 is held: 2,048 rows an expert at K 2304 / N 896 and its transpose, every layer
(costs_mellum2.grouped_matmul_cost; the step's `pairs_elsewhere`) (%)."""

from chipbench import readers_mellum2


def read(run):
    return readers_mellum2.expert_matmul_roofline(run)
