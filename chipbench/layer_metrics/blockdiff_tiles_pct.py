"""Kernels: (q block, sub-tile) visits a head of the flash kernels under the block-diffusion mask,
% of the visits a causal walk over 2L rows and 2L keys makes (the step's own report:
`diff_tiles_visited` / `diff_tiles_causal`, ops/flash.py::blockdiff_tiles). ONE walk over 2L x 2L
at tiles of 512 cannot go under (n^2 + 2n) / (2n^2 + n) = 54.5% (n = 16); the program's walk over
the L clean keys alone visits n^2 + n of 2n^2 + n = 51.5%, the noised blocks' own keys being no
tile of any kernel."""

from chipbench import readers_sdar


def read(run):
    return readers_sdar.blockdiff_tiles_pct(run)
