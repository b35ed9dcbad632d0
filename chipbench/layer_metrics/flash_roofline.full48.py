"""Kernels: time of the `attn.attend.N` flash kernels (Laguna's full-attention layers', forward
and backward) in the traced steps against max(operations / 197e12, bytes / 819e9) of the causal
pairs at those layers' own head count and the explicit head_dim (costs_laguna.flash_cost) (%)."""

from chipbench import readers_laguna


def read(run):
    return readers_laguna.flash_roofline(run, "attn.attend")
