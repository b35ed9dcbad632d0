"""Kernels: grouped-matmul kernel time in the traced steps (forward, input and weight gradients,
recomputed ones too) against max(operations / 197e12, bytes / 819e9) of the nine matmuls a layer
requires, from shapes (%); compute-bound at these sizes."""

from chipbench import readers_moe


def read(run):
    return readers_moe.expert_matmul_roofline(run)
