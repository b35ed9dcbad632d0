"""The table of peaks and the operation / byte counts, from shapes.

These are what the algorithm NEEDS, not what a compiler emitted:
recomputed operations (remat) do not count, and bytes are the least a
kernel must move. `shape` is a configuration file's dict (HF key
names). Each function has a hand-worked case in tests/chipbench.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str, path: str = os.path.join(_HERE, "peaks.json")) -> dict:
    """Peaks of one chip, by `device_kind` as JAX reports it. A device
    that is not in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {path}; add a row with its source"
        )
    return table[device_kind]


def head_dim(shape: dict) -> int:
    return shape.get("head_dim") or shape["hidden_size"] // shape["num_attention_heads"]


def matmul_params(shape: dict) -> dict:
    """Parameters that sit in a matrix multiplication on the token path:
    per layer q,k,v,o + gate,up,down; plus the output head. The
    embedding is a gather and the norms are elementwise: neither counts."""
    d, f = shape["hidden_size"], shape["intermediate_size"]
    hd = head_dim(shape)
    h, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    layer = d * hd * (2 * h + 2 * kv) + 3 * d * f
    head = d * shape["vocab_size"]
    return {"layer": layer, "head": head,
            "total": shape["num_hidden_layers"] * layer + head}


def attn_flops_causal(shape: dict, seq_len: int) -> float:
    """Forward FLOPs of causal attention for ONE sequence, all layers:
    QK^T and PV are 2*hd FLOPs per (query, key) pair per head each, and
    a causal mask leaves S*(S+1)/2 pairs."""
    pairs = seq_len * (seq_len + 1) / 2
    return (shape["num_hidden_layers"] * shape["num_attention_heads"]
            * 4.0 * head_dim(shape) * pairs)


def train_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward + backward FLOPs a training token requires: 6 per matmul
    parameter (2 forward, 4 backward) plus 3x the causal attention
    forward, averaged over the sequence. Recompute is not counted."""
    return (6.0 * matmul_params(shape)["total"]
            + 3.0 * attn_flops_causal(shape, seq_len) / seq_len)


def flash_cost(shape: dict, batch: int, seq_len: int, io_bytes: int = 2) -> dict:
    """Operations and least bytes of the flash kernel over ONE layer's
    call at [batch, seq_len], forward and backward apart.

    forward: 4*hd FLOPs per causal (q, k) pair per head; reads Q, K, V,
    writes O. backward: recomputes the scores and forms dV, dP, dQ, dK:
    5 matmuls of 2*hd each against the forward's 2, so 2.5x; reads
    Q, K, V, O, dO and writes dQ, dK, dV.
    """
    hd = head_dim(shape)
    h, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    pairs = seq_len * (seq_len + 1) / 2
    fwd = batch * h * 4.0 * hd * pairs
    q_bytes = batch * seq_len * h * hd * io_bytes
    kv_bytes = batch * seq_len * kv * hd * io_bytes
    return {
        "fwd_flops": fwd,
        "bwd_flops": 2.5 * fwd,
        "fwd_bytes": 2 * q_bytes + 2 * kv_bytes,            # Q, O + K, V
        "bwd_bytes": 4 * q_bytes + 4 * kv_bytes,            # Q, O, dO, dQ + K, V, dK, dV
    }


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
