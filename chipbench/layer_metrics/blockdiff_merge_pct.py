"""Attention: device time of what the block-diffusion mask costs OUTSIDE the flash kernels, the scope
`flash.blockdiff_merge` of ops/flash.py::_block_diffusion (each noised block's own [4, 4] product in
float32 and the log-sum-exp merge with the kernels' (o, lse), forward and backward: slices, copies
and converts of [.., 2048, 4, 128] views, a block of 4 rows being half a tile of 8 sublanes), % of
the traced window's busy time. A family of its own (chipbench/step_scopes/sdar.json), so
`attn_share_pct` of this cell is the projections, the rotary and the kernels without it."""

from chipbench import readers_sdar


def read(run):
    return readers_sdar.blockdiff_merge_pct(run)
