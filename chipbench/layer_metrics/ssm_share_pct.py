"""State-space mixer: share of the traced steps' device time booked to the `ssm.*` scopes of the
Mamba-2 layers (models/nemotron_h.py): the input projection, the causal convolution with its bias
and SiLU, the step and the decay, the selective scan over the chunks (ops/ssd.py), the gated group
norm, the output projection; forward and backward (%). None without a trace, the record or such a
scope."""

from chipbench import readers_nemotron_h


def read(run):
    return readers_nemotron_h.families_pct(run)
