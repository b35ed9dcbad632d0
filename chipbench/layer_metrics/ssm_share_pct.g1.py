"""State-space mixer: share of the traced steps' device time booked to the `ssm.*` scopes of the
Mamba-2 layers at ONE group of 64 heads (models/granite_hybrid.py over models/nemotron_h.py's
sublayer): the input projection, the causal convolution that stops at a document's boundary, the
step and the decay, the selective scan under the reset (ops/ssd.py), the gated norm over 4,096
channels, the output projection; forward, the forward made again under remat "full", and backward
(%). None without a trace, the record or such a scope."""

from chipbench import readers_granite_hybrid


def read(run):
    return readers_granite_hybrid.families_pct(run)
