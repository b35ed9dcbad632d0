"""Kernels: time of all grouped-matmul kernels in the traced steps against the NINE matmuls an
expert layer (three forward, six backward) over the (token, expert) pairs ACTUALLY routed to the
held experts, eight pairs a token of which about 1 in 32 is held, four expert layers
(costs_kimi_linear.grouped_matmul_cost at K 2304 / N 1024, 8 held of 256; the step's
`pairs_elsewhere`) (%)."""

from chipbench import readers_kimi_linear


def read(run):
    return readers_kimi_linear.expert_matmul_roofline_held(run)
