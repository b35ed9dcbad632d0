"""Attention: share of the traced steps' device time under `attn.gate` and `swa.gate`: the
headwise output gate's [D, heads] product, its sigmoid, the multiply where `wo` reads o, and
their gradients (%): what the gate costs a step. None without a trace or such a scope."""

from chipbench import readers_laguna


def read(run):
    return readers_laguna.family_pct(run, "gate")
