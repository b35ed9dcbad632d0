"""State-space mixer: the part of ssm_share_pct.g1 under `ssm.scan` alone: the selective scan of ONE
group of 64 heads under the documents' reset, the two kernels, the forward kernel run again under
the block's remat, and the [heads, S] arithmetic of dt A (% of the traced steps' device time). What
the scan costs beyond the mixer's matmuls."""

from chipbench import readers_granite_hybrid


def read(run):
    return readers_granite_hybrid.families_pct(run, ("ssm_scan",))
