"""Kernels: time of the `dsa.attend.N` flash kernels (forward, dq and dk/dv under the packed
selection) in the traced steps against max(operations / 197e12, bytes / 819e9) of the SELECTED
(query, key) pairs alone (costs_keye.flash_cost: t + 1 for a query before `topk`, `topk` from
there on) (%). Kernels that visit every sub-tile under the diagonal and mask inside it walk
4096.5 keys a query at 8192 where 1792 are selected: they cannot read over 44."""

from chipbench import readers_keye


def read(run):
    return readers_keye.flash_roofline_selected(run)
