"""Attention: the part of mla_share_pct under `mla.glue` alone: the splits, the rotary on the
64-wide parts, the broadcast of the one rotary key over the heads, the concatenations (% of the
traced steps' device time). What MLA costs beyond its matmuls and the kernel."""

from chipbench import readers_glm_lite


def read(run):
    return readers_glm_lite.scope_share_pct(run, "mla.glue")
