"""Model programs: share of the traced steps' device time booked to `dense.ffn`, the dense SwiGLU
(its `tp` rings under a mesh), forward and backward (%). None without a trace or the record."""

from chipbench import readers_step


def read(run):
    return readers_step.family_pct(run, "ffn")
