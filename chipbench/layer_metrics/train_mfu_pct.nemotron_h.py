"""Train step: train_tok_s x the operations this chip's share requires of a token
(costs_nemotron_h: the Mamba mixers' projections and the scan in its position-by-position count,
the attention layer's projections and causal scores, the router, the shared expert, the routed
experts x the measured share of pairs held, the head over the held columns; three times forward;
recompute not counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_nemotron_h


def read(run):
    return readers_nemotron_h.train_mfu_pct(run)
