"""Attention: share of the traced steps' device time booked to the RMSNorm a head on q and k of SDAR's
full layers (`attn.norm`: models/laguna.py under `qk_head_norm`, over BOTH copies' rows), forward and
backward (%): `qk_norm_pct`'s reading for this model, whose own reader answers Mellum2's runs alone.
0.0 where XLA fused all of it into a neighbour's pass; None without a trace, the record or the
scopes."""

from chipbench import readers_sdar


def read(run):
    return readers_sdar.qk_norm_pct(run)
