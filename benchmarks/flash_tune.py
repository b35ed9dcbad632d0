"""Tune flash_attention block sizes at the flagship bench shape.

Chained fwd+bwd timing (single fence at the end, so per-call host
syncs do not pad the kernel time).
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash import flash_attention

B = int(os.environ.get("TUNE_B", 8))
S = int(os.environ.get("TUNE_S", 1024))
H, KV, D = 16, 8, 64


def bench(fn, q, k, v, iters=30):
    g = jax.jit(jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                         argnums=(0, 1, 2)))
    dq, dk, dv = g(q, k, v)
    float(jnp.asarray(dq).ravel()[0])  # fenced warmup
    outs = []
    t0 = time.perf_counter()
    for _ in range(iters):
        dq, dk, dv = g(dq, k, v)  # chain dq -> q so steps are dependent
        outs.append(dq)
    float(jnp.asarray(outs[-1]).ravel()[0])
    return (time.perf_counter() - t0) / iters


def main():
    from ray_tpu.utils.backend import open_backend

    open_backend()
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, S, KV, D), jnp.bfloat16)

    dt = bench(functools.partial(xla_attention, causal=True), q, k, v)
    print(json.dumps({"tag": "xla", "S": S, "fwdbwd_ms": round(dt * 1e3, 2)}),
          flush=True)

    cfgs = [(bq, bk, f) for bk in (1024, 2048, 4096) if bk <= S
            for bq in (256, 512, 1024) for f in (1, 2)]
    if S < 1024:
        cfgs = [(512, S, 1), (512, S, 2)]
    for bq, bk, fold in cfgs:
        try:
            f = functools.partial(
                flash_attention, causal=True, block_q=bq, block_k=bk,
                fold_heads=fold, interpret=False,
            )
            dt = bench(f, q, k, v)
            print(json.dumps({"tag": f"flash_{bq}x{bk}_f{fold}", "S": S,
                              "fwdbwd_ms": round(dt * 1e3, 2)}), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"tag": f"flash_{bq}x{bk}_f{fold}", "S": S,
                              "error": repr(e)[:160]}), flush=True)


if __name__ == "__main__":
    main()
