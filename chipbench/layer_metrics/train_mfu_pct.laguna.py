"""Train step: train_tok_s x the operations this chip's share requires of a token
(costs_laguna: every layer's projections and gate at its own head count, the scores of the
pairs its mask leaves visible, the dense layer, router, shared expert, the routed experts x the
measured share of pairs held, the head; recompute not counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_laguna


def read(run):
    return readers_laguna.train_mfu_pct(run)
