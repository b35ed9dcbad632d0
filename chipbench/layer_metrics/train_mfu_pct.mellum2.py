"""Train step: train_tok_s x the operations this chip's share requires of a token
(costs_mellum2: every layer's projections at 32 / 4 heads of 128, the scores of the pairs its
mask leaves visible (the window's 1,024 or every key before the row), the router, the routed
experts x the measured share of pairs held, the head slice; recompute not counted) over chips x
peak FLOP/s (%)."""

from chipbench import readers_mellum2


def read(run):
    return readers_mellum2.train_mfu_pct(run)
