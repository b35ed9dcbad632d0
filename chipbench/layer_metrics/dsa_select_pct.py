"""Attention: share of the traced steps' device time under `dsa.select`: the exact top-2048 of
each query's index scores (32 counting passes over a chunk of queries, the cut among equal scores
where one fell there) and the packing of the mask, a bit a pair (%). None without a trace or such
a scope."""

from chipbench import readers_keye


def read(run):
    return readers_keye.families_pct(run, ("dsa_select",))
