"""Attention: share of the traced steps' device time booked to the `gdn.*` scopes of the
linear-attention layers (models/olmo_hybrid.py): the projections, the causal convolution with
SiLU and the L2 norms, beta and the decay, the gated delta rule over the chunks
(ops/gated_delta.py), the gated output norm, the output projection; forward and backward (%).
None without a trace, the record or such a scope."""

from chipbench import readers_olmo_hybrid


def read(run):
    return readers_olmo_hybrid.families_pct(run)
