"""Attention: share of the traced steps' device time booked to the `dsa.*` scopes of the
sublayer of models/dsa.py: projections, the RMSNorm a head, rotary, the indexer's projections and
scores, the exact top-k and its packed mask, the flash kernels under the selection
(`dsa.attend.N`), output projection; forward and backward (%). None without a trace, the record
or such a scope."""

from chipbench import readers_keye


def read(run):
    return readers_keye.families_pct(run, readers_keye.FAMILIES)
