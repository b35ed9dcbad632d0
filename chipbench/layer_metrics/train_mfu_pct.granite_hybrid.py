"""Train step: train_tok_s x the operations this chip's share requires of a token
(costs_granite_hybrid: the Mamba mixers' projections and the scan in its position-by-position count,
the attention layer's projections and its scores over the VISIBLE pairs of the window's own
batches, ten SwiGLUs, the head over the held rows of the tied table; three times forward; the
block's forward made again under remat "full" not counted) over chips x peak FLOP/s (%)."""

from chipbench import readers_granite_hybrid


def read(run):
    return readers_granite_hybrid.train_mfu_pct(run)
