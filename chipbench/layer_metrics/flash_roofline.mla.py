"""Kernels: forward + backward flash kernel time in the traced steps against the larger of
operations / peak FLOP/s and bytes / peak bytes/s for one call a dense, expert and MTP block at
20 heads of 256, none shared (costs_glm_lite.flash_cost) (%)."""

from chipbench import readers_glm_lite


def read(run):
    return readers_glm_lite.flash_roofline(run)
