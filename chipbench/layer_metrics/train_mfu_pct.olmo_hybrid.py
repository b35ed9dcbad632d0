"""Train step: train_tok_s x the operations a token requires (costs_olmo_hybrid: the linear
mixers' projections and the recurrence in its position-by-position count, the full mixer's
projections and causal scores, every SwiGLU, the head over the held columns; recompute not
counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_olmo_hybrid


def read(run):
    return readers_olmo_hybrid.train_mfu_pct(run)
