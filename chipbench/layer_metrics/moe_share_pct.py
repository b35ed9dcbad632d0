"""Expert layer: share of the traced steps' device time in ops under the program's `moe.*`
named scopes (router, dispatch, experts, combine; forward and backward) or in the grouped-matmul
kernels (%). None without a trace or where no op ran under such a scope."""

from chipbench import readers_moe


def read(run):
    got = readers_moe.moe_seconds(run)
    return None if got is None else readers_moe.share_pct(
        run, sum(got["scoped"].values()) + got["kernels"])
