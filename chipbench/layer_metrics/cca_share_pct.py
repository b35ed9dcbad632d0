"""Attention: share of the traced steps' device time in ops under the program's `cca.*` named
scopes (projections, the convolution mix, the output projection; forward and backward) or in the
flash kernels (%). None without a trace or where no op ran under such a scope."""

from chipbench import readers_zaya


def read(run):
    got = readers_zaya.cca_seconds(run)
    return None if got is None else readers_zaya.share_pct(
        run, sum(got["scoped"].values()) + got["kernels"])
