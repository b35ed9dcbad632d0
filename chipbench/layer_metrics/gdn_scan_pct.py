"""Attention: the part of gdn_share_pct under `gdn.scan` alone: the gated delta rule, the
chunks' products and triangular solve and the loops over the chunks, forward, the rematerialised
forward and the transpose (% of the traced steps' device time). What the recurrence costs
beyond the mixer's matmuls."""

from chipbench import readers_olmo_hybrid


def read(run):
    return readers_olmo_hybrid.families_pct(run, ("gdn_scan",))
