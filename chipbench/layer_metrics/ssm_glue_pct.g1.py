"""State-space mixer: the part of ssm_share_pct.g1 under `ssm.conv`, `ssm.gates` and `ssm.norm`: the
causal convolution of 4 taps that stops at a document's boundary with its bias and SiLU, the
step's softplus and the decay, the gated norm (% of the traced steps' device time): what is neither a
projection nor the scan."""

from chipbench import readers_granite_hybrid


def read(run):
    return readers_granite_hybrid.families_pct(run, ("ssm_glue",))
