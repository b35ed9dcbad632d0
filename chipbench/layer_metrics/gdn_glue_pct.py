"""Attention: the part of gdn_share_pct under `gdn.conv`, `gdn.gates` and `gdn.norm`: the causal
convolution of 4 taps with SiLU and the L2 norms, beta and the log decay, the gated output norm
(% of the traced steps' device time): what is neither a projection nor the recurrence."""

from chipbench import readers_olmo_hybrid


def read(run):
    return readers_olmo_hybrid.families_pct(run, ("gdn_glue",))
