"""Kernels: time of all grouped-matmul kernels in the traced steps against the NINE matmuls a
layer (three forward, six backward) over the (token, expert) pairs ACTUALLY routed to the held
experts, eight pairs a token of which about 1 in 40 is held, every layer
(costs_solar_open2.grouped_matmul_cost at K 4096 / N 1280, 8 held of 320; the step's
`pairs_elsewhere`) (%)."""

from chipbench import readers_solar_open2


def read(run):
    return readers_solar_open2.expert_matmul_roofline_held(run)
