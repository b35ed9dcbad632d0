"""Split flash kernel timing: fwd-only vs fwd+bwd, chained fencing."""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash import flash_attention

B, S, H, KV, D = 8, 1024, 16, 8, 64


def chain_fwd(fn, q, k, v, iters=50):
    f = jax.jit(lambda q, k, v: fn(q, k, v))
    o = f(q, k, v)
    float(jnp.asarray(o).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        o = f(o, k, v)  # output feeds q: dependent chain
    float(jnp.asarray(o).ravel()[0])
    return (time.perf_counter() - t0) / iters


def chain_bwd(fn, q, k, v, iters=50):
    g = jax.jit(jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                         argnums=0))
    dq = g(q, k, v)
    float(jnp.asarray(dq).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        dq = g(dq, k, v)
    float(jnp.asarray(dq).ravel()[0])
    return (time.perf_counter() - t0) / iters


def main():
    from ray_tpu.utils.backend import open_backend

    open_backend()
    q = jax.random.normal(jax.random.key(0), (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, S, KV, D), jnp.bfloat16)
    out = {}
    fl = functools.partial(flash_attention, causal=True, interpret=False)
    xa = functools.partial(xla_attention, causal=True)
    out["flash_fwd_ms"] = round(1e3 * chain_fwd(fl, q, k, v), 3)
    out["xla_fwd_ms"] = round(1e3 * chain_fwd(xa, q, k, v), 3)
    out["flash_fwd_dq_ms"] = round(1e3 * chain_bwd(fl, q, k, v), 3)
    out["xla_fwd_dq_ms"] = round(1e3 * chain_bwd(xa, q, k, v), 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
