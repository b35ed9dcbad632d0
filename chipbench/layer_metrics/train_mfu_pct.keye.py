"""Train step: train_tok_s x the operations this chip's share requires of a token (costs_keye:
projections, router, the attention over the SELECTED pairs, the routed experts x the measured
share of pairs held and the head, three times forward; the indexer's projections and scores
once, since nothing of them is differentiated; recompute not counted) over chips x peak FLOP/s
(%)."""

from chipbench import readers_keye


def read(run):
    return readers_keye.train_mfu_pct(run)
