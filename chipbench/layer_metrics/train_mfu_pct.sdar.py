"""Train step: train_tok_s (DATA tokens, 8,192 a step) x the operations this chip's share requires
of a data token under block diffusion (costs_sdar: BOTH copies' projections at 32 / 4 heads of
128, the scores of the L (L + 4) visible pairs a head, both copies' router and routed experts x
the measured share of pairs held, the head on the L noised rows; recompute not counted) over chips
x peak FLOP/s (%)."""

from chipbench import readers_sdar


def read(run):
    return readers_sdar.train_mfu_pct(run)
