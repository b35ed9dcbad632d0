"""State-space mixer: the part of ssm_share_pct under `ssm.scan` alone: the selective scan, the
chunks' products and the loop over the chunks, forward, the rematerialised forward and the
transpose (% of the traced steps' device time). What the scan costs beyond the mixer's matmuls."""

from chipbench import readers_nemotron_h


def read(run):
    return readers_nemotron_h.families_pct(run, ("ssm_scan",))
