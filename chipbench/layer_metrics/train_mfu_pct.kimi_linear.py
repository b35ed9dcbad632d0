"""Train step: train_tok_s x the operations this chip's share requires of a token
(costs_kimi_linear: four KDA mixers' projections at all 32 heads and the rule in its
position-by-position count, the MLA layer's projections and causal scores at keys of 192 and
values of 128, the dense SwiGLU of 9216, the router, the shared expert, the routed experts x the
measured share of pairs held, the head over the held columns; three times forward; recompute not
counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_kimi_linear


def read(run):
    return readers_kimi_linear.train_mfu_pct(run)
