"""KDA mixer: the part of kda_share_pct under `kda.conv`, `kda.gates` and `kda.norm`: the causal
convolution of 4 taps with its SiLU and L2 norms, beta and the per-channel log decay, the
sigmoid-gated output norm (% of the traced steps' device time): what is neither a projection nor
the rule."""

from chipbench import readers_solar_open2


def read(run):
    return readers_solar_open2.families_pct(run, ("kda_glue",))
