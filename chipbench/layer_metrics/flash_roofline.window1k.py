"""Kernels: time of the `swa.attend.N` flash kernels (the three sliding-window layers': forward,
and at 16,384 keys the dq and the dk/dv kernels apart, four kv blocks of 4,096) in the traced
steps against max(operations / 197e12, bytes / 819e9) of the (query, key) pairs INSIDE the window
of 1,024 (costs_mellum2.flash_cost) (%). A forward that walks whole 512-key sub-tiles under q
blocks of 512 visits three or four where two sub-tiles' worth of pairs is required, and the dq
kernel takes a whole [512, 4096] kv block a step: it cannot read near 100."""

from chipbench import readers_mellum2


def read(run):
    return readers_mellum2.flash_roofline(run, "swa.attend")
