"""Attention: share of the traced steps' device time under the program's `mla.*` named scopes
(down and up projections, glue, the flash kernels called in `mla.attend`, output projection;
forward and backward) in the dense and expert layers (%); the MTP block's own is in
mtp_share_pct. None without a trace or where nothing ran under such a scope."""

from chipbench import readers_glm_lite


def read(run):
    return readers_glm_lite.scope_share_pct(run, "mla.")
