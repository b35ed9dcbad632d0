"""KDA mixer: the part of kda_share_pct under `kda.scan` alone: the rule, the chunks' decayed
products and solve and the loop over the chunks, forward, the rematerialised forward and the
transpose (% of the traced steps' device time). What the recurrence costs beyond the mixer's
matmuls."""

from chipbench import readers_solar_open2


def read(run):
    return readers_solar_open2.families_pct(run, ("kda_scan",))
