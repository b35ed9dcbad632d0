"""Attention: share of the traced steps' device time booked to the `attn.*` scopes of the full
causal GQA block: projections (the `tp` rings under them), q/k norm and rotary, the flash kernels,
output projection and residual; forward and backward (%). None without a trace or the record."""

from chipbench import readers_step


def read(run):
    return readers_step.family_pct(run, "attn")
