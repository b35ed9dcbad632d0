"""Attention: share of the traced steps' device time booked to the RMSNorm a head on q and k
(`swa.norm`, `attn.norm`: models/laguna.py under `qk_head_norm`), forward and backward (%): what
the reading of the config that has the norm costs a step. 0.0 where XLA fused all of it into a
neighbour's pass; None without a trace, the record or the scopes."""

from chipbench import readers_mellum2


def read(run):
    return readers_mellum2.qk_norm_pct(run)
