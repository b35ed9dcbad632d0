"""Attention: the part of cca_share_pct under `cca.mix` alone: both causal convolutions, the q-k
mean, the L2 norms with the temperature, the rotary and the value shift (% of the traced steps'
device time). What CCA costs beyond its projections and the kernel."""

from chipbench import readers_zaya


def read(run):
    got = readers_zaya.cca_seconds(run)
    return None if got is None else readers_zaya.share_pct(run, got["scoped"].get("cca.mix", 0.0))
