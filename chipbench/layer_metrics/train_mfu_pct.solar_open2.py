"""Train step: train_tok_s x the operations this chip's share requires of a token
(costs_solar_open2: the KDA mixers' projections over the held heads and the rule in its
position-by-position count, the GQA layer's projections and causal scores over the held heads, the
router, the shared expert, the routed experts x the measured share of pairs held, the head over
the held columns; three times forward; recompute not counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_solar_open2


def read(run):
    return readers_solar_open2.train_mfu_pct(run)
