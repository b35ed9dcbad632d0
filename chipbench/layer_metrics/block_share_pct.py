"""Model programs: share of the traced steps' device time booked to the layer stack itself and to
the blocks' norms where no projection took them in: the scan's slices of the stacked weights and
saved residuals, its stacked writes and zero fills, the loop's control (`block.stack`, a shallow
scope: chipbench/readers_step.py), and `block.norm` (%). None without a trace or the record."""

from chipbench import readers_step


def read(run):
    return readers_step.family_pct(run, "block")
