"""Train step: share of the traced steps' device time under the program's `mtp.*` named scopes:
the merge, the MTP block (its MLA, flash and grouped-matmul kernels, expert layer and shared
expert) and the second pass of the head (%). What multi-token prediction costs a step."""

from chipbench import readers_glm_lite


def read(run):
    return readers_glm_lite.scope_share_pct(run, "mtp.")
