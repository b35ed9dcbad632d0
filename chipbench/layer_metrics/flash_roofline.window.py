"""Kernels: time of the `swa.attend.N` flash kernels (the sliding-window layers', forward and
backward) in the traced steps against max(operations / 197e12, bytes / 819e9) of the (query,
key) pairs INSIDE the window, at the layers' own head count (costs_laguna.flash_cost) (%).
A kernel that walks whole 512-key sub-tiles visits 15 where 7.5 sub-tiles' worth of pairs is
required at 4096 keys and a window of 512: it cannot read over 50."""

from chipbench import readers_laguna


def read(run):
    return readers_laguna.flash_roofline(run, "swa.attend")
