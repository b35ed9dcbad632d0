"""Ring/Ulysses attention ON THE CHIP vs flash (round-5 verdict #10).

One v5e chip: the `sp` axis has size 1, so the ppermute is an identity
hop and the scan makes exactly one ring step — what this measures is
the ring BODY's on-chip cost (blockwise online-softmax in plain XLA)
against the Pallas flash kernel and XLA attention at the same shape.
The multi-chip overlap question needs real ICI; the CPU-mesh tests
cover numerics, this covers single-chip kernel viability.

Chained fwd+bwd timing, one fence.
Prints one JSON line per (S, impl); writes RINGBENCH json artifact.
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ray_tpu.ops.attention import xla_attention
from ray_tpu.ops.flash import flash_attention
from ray_tpu.ops.ring_attention import ring_attention_spmd

H, KV, D = 16, 8, 64


def bench(fn, q, k, v, iters=20):
    g = jax.jit(jax.grad(
        lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)
    ))
    dq, dk, dv = g(q, k, v)
    float(jnp.asarray(dq).ravel()[0])  # fenced warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        dq, dk, dv = g(dq, k, v)  # chain dq -> q: dependent steps
    float(jnp.asarray(dq).ravel()[0])
    return (time.perf_counter() - t0) / iters


def ring_forced(mesh):
    """Ring body under shard_map on the 1-device sp axis (the wrapper
    would fall back to xla_attention at sp=1 — bypass it)."""

    def fn(q, k, v):
        spec = jax.sharding.PartitionSpec(None, "sp", None, None)
        return jax.shard_map(
            functools.partial(ring_attention_spmd, axis_name="sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return fn


def main():
    from ray_tpu.utils.backend import open_backend

    dev = open_backend()
    mesh = Mesh(np.asarray([dev]), ("sp",))
    results = []
    from jax.sharding import NamedSharding, PartitionSpec

    ring_sharding = NamedSharding(mesh, PartitionSpec(None, "sp", None, None))
    for B, S in ((2, 4096), (1, 8192)):
        q = jax.random.normal(jax.random.key(0), (B, S, H, D), jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (B, S, KV, D), jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (B, S, KV, D), jnp.bfloat16)
        # arrays must live PRE-SHARDED on the ring layout: an unsharded
        # arg makes jit reshard per call, which swamps the kernel — real
        # training arrays are born sharded, so the bench's must be too
        q, k, v = (jax.device_put(x, ring_sharding) for x in (q, k, v))
        impls = {
            "ring_sp1": ring_forced(mesh),
            "flash": functools.partial(flash_attention, causal=True),
            "xla": functools.partial(xla_attention, causal=True),
        }
        for tag, fn in impls.items():
            try:
                dt = bench(fn, q, k, v)
                rec = {"tag": tag, "B": B, "S": S,
                       "fwdbwd_ms": round(dt * 1e3, 2)}
            except Exception as e:  # noqa: BLE001
                rec = {"tag": tag, "B": B, "S": S, "error": repr(e)[:160]}
            print(json.dumps(rec), flush=True)
            results.append(rec)
    with open("benchmarks/RINGBENCH_r05.json", "w") as f:
        json.dump({"device": getattr(dev, "device_kind", str(dev)),
                   "rows": results}, f, indent=1)


if __name__ == "__main__":
    main()
