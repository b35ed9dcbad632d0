"""Kernels: time of all grouped-matmul kernels in the traced steps against the nine matmuls over
the (token, expert) pairs ACTUALLY routed to the held experts, four pairs a token, every expert
block and the MTP block's (costs_glm_lite.grouped_matmul_cost; the step's `pairs_elsewhere`) (%)."""

from chipbench import readers_glm_lite


def read(run):
    return readers_glm_lite.expert_matmul_roofline_held(run)
