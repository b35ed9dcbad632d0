"""Expert layer: the part of moe_share_pct outside the grouped-matmul kernels: router, sort,
gathers to expert order and back, the weighted sum, the experts' elementwise ops (% of the traced
steps' device time). What a dropless dispatch costs beside the matmuls it feeds."""

from chipbench import readers_moe


def read(run):
    got = readers_moe.moe_seconds(run)
    return None if got is None else readers_moe.share_pct(run, sum(got["scoped"].values()))
