"""Kernels: time of all grouped-matmul kernels in the traced steps against the SIX matmuls a layer
(relu^2 experts have no gate: two forward, four backward) over the (token, expert) pairs ACTUALLY
routed to the held experts, six pairs a token of which about 1 in 16 is held, every expert layer
(costs_nemotron_h.grouped_matmul_cost at K 2688 / N 1856, 8 held; the step's `pairs_elsewhere`)
(%)."""

from chipbench import readers_nemotron_h


def read(run):
    return readers_nemotron_h.expert_matmul_roofline_held(run)
