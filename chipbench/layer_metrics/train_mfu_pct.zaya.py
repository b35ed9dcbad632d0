"""Train step: train_tok_s x the operations this chip's share requires of a token (costs_zaya:
CCA's projections and grouped convolution, causal scores over the latent's heads, the router's
MLP, the experts x the measured share of pairs held here, the head over the held rows; recompute
not counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_zaya


def read(run):
    return readers_zaya.train_mfu_pct(run)
