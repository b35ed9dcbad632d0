"""State-space mixer: the part of ssm_share_pct under `ssm.conv`, `ssm.gates` and `ssm.norm`: the
causal convolution of 4 taps with its bias and SiLU, the step's softplus and the decay, the gated
group norm (% of the traced steps' device time): what is neither a projection nor the scan."""

from chipbench import readers_nemotron_h


def read(run):
    return readers_nemotron_h.families_pct(run, ("ssm_glue",))
