"""Train step: train_tok_s x the operations this chip's share requires of a token
(costs_glm_lite: MLA's projections and causal scores in every block, the dense layer, router,
shared expert, the routed experts x the measured share of pairs held, the MTP merge, the head
twice; recompute not counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_glm_lite


def read(run):
    return readers_glm_lite.train_mfu_pct(run)
