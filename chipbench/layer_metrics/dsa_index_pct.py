"""Attention: share of the traced steps' device time under `dsa.index.proj` and
`dsa.index.scores`: the indexer's three projections, its LayerNorm and rotary, and the scores of
every (query, key) pair under the diagonal past the first `topk` rows, 16 heads of 64 summed under
a ReLU in float32 (%): forward only, nothing of it is differentiated. None without a trace or
such a scope."""

from chipbench import readers_keye


def read(run):
    return readers_keye.families_pct(run, ("dsa_index",))
