"""Kernels: time of the `flash.blockdiff.N` kernels (the flash forward and fused backward under
the block-diffusion mask: 2L = 16,384 rows against the L = 8,192 clean keys, one kv block, a
prefix of sub-tiles a q block) in the traced steps against max(operations / 197e12, bytes /
819e9) of the L (L + 4) pairs a head the mask leaves VISIBLE at 32 / 4 heads of 128, two matmuls
a pair forward and five backward (costs_sdar.flash_cost: the count does not depend on which tiles
the program visits, and includes the noised blocks' own pairs, which the program computes outside
the kernels) (%)."""

from chipbench import readers_sdar


def read(run):
    return readers_sdar.flash_roofline(run)
