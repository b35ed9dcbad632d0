"""KDA mixer: share of the traced steps' device time booked to the `kda.*` scopes of the
Kimi-Delta-Attention layers (models/solar_open2.py): the projections and low-rank pairs, the
causal convolution with its SiLU and L2 norms, beta and the per-channel log decay, the rule over
the chunks (ops/kda.py), the sigmoid-gated norm, the output projection; forward and backward (%).
None without a trace, the record or such a scope."""

from chipbench import readers_solar_open2


def read(run):
    return readers_solar_open2.families_pct(run)
