"""Attention: share of the traced steps' device time booked to the `swa.*` scopes of the
sliding-window layers (models/laguna.py): projections, rotary on the whole head, the window
flash kernels (`swa.attend.N`), output projection and residual; forward and backward (%). The
gate is apart (attn_gate_pct). None without a trace, the record or such a scope."""

from chipbench import readers_laguna


def read(run):
    return readers_laguna.family_pct(run, "swa")
