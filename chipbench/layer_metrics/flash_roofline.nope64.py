"""Kernels: time of the `attn.attend.N` flash kernels (Granite 4.0-H's GQA layer at 32 query heads
over 8 key-value heads of 64, no rotary, 8,192 keys under the documents' mask; forward, the forward
run again under remat, and backward) in the traced steps against max(operations / 197e12, bytes /
819e9) of the pairs the traced batches' own documents leave VISIBLE
(costs_granite_hybrid.flash_cost) (%)."""

from chipbench import readers_granite_hybrid


def read(run):
    return readers_granite_hybrid.flash_roofline(run)
