"""Bisect the train-step wall time: matmul peak, fwd, fwd+bwd, full step —
then the full roofline attribution (ray_tpu.profiler).

Diagnostic harness for MFU work; prints one JSON line per probe, then
writes the segment-attributed StepProfile to
benchmarks/PROFILE_trainstep_r06.json (--out to override, --no-roofline
to skip). Platform-aware: the flagship LLAMA_400M shapes on TPU, the
smoke LLAMA_TINY shapes under JAX_PLATFORMS=cpu.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import llama
from ray_tpu.train.step import TrainState, make_train_step


def _fence(r):
    """Hard fence: pull one element to the host — a host transfer of
    data DEPENDENT on the result cannot return before the compute graph
    has executed."""
    leaf = jax.tree.leaves(r)[0]
    return float(jnp.asarray(leaf).ravel()[0])


def timeit(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        _fence(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        _fence(fn(*args))
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-roofline", action="store_true",
                    help="skip the ray_tpu.profiler attribution pass")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "PROFILE_trainstep_r06.json",
    ))
    args = ap.parse_args()

    from ray_tpu.utils.backend import open_backend

    on_tpu = open_backend().platform == "tpu"
    out = {}
    # 1) achievable bf16 matmul peak through this backend
    for n in ((2048, 4096, 8192) if on_tpu else (512, 1024)):
        a = jnp.ones((n, n), jnp.bfloat16)
        b = jnp.ones((n, n), jnp.bfloat16)
        f = jax.jit(lambda a, b: a @ b)
        dt = timeit(f, a, b, iters=20 if on_tpu else 5)
        out[f"matmul{n}_tflops"] = round(2 * n**3 / dt / 1e12, 1)

    # 2) model-shaped probes
    if on_tpu:
        cfg = dataclasses.replace(
            llama.LLAMA_400M, attention_impl="xla", remat_policy="dots"
        )
        B, S = 8, 1024
    else:
        cfg, B, S = llama.LLAMA_TINY, 4, 64
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    fwd = jax.jit(lambda p, b: llama.loss_fn(p, b, cfg))
    out["fwd_ms"] = round(1e3 * timeit(fwd, params, batch, iters=10), 2)

    vg = jax.jit(lambda p, b: jax.value_and_grad(llama.loss_fn)(p, b, cfg))
    dt = timeit(vg, params, batch, iters=10)
    out["fwd_bwd_ms"] = round(1e3 * dt, 2)

    # 3) forward WITHOUT the lm-head/loss (isolate the vocab matmul + CE)
    fwd_nohead = jax.jit(
        lambda p, t: llama.forward(p, t, cfg).astype(jnp.bfloat16).sum()
    )
    out["fwd_with_head_sum_ms"] = round(
        1e3 * timeit(fwd_nohead, params, batch["tokens"], iters=10), 2
    )

    # 4) attention-only probe: one layer's xla attention fwd at [B,S,H,D]
    from ray_tpu.ops.attention import attention

    q = jnp.ones((B, S, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    k = jnp.ones((B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    v = jnp.ones((B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    att = jax.jit(lambda q, k, v: attention(q, k, v, causal=True, impl="xla"))
    out["xla_attn_layer_ms"] = round(1e3 * timeit(att, q, k, v, iters=20), 2)
    if on_tpu:
        att_f = jax.jit(lambda q, k, v: attention(q, k, v, causal=True, impl="flash"))
        out["flash_attn_layer_ms"] = round(1e3 * timeit(att_f, q, k, v, iters=20), 2)

    # 5) full donated train step (donation deletes `params` — the
    # roofline pass below copies internally, so run this first)
    opt = optax.adamw(3e-4)
    state = TrainState.create(params, opt)
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt)
    for _ in range(2):
        state, m = step(state, batch)
        float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(10):
        state, m = step(state, batch)
        float(m["loss"])
    out["step_ms"] = round(1e3 * (time.perf_counter() - t0) / 10, 2)

    # 6) roofline attribution: the op-level breakdown the bisection
    # above can't give — every ms named, classified, and serialized
    if not args.no_roofline:
        from ray_tpu.profiler import profile_train_step

        prof = profile_train_step(
            cfg, llama.init_params(cfg, jax.random.key(0)), batch, opt,
            iters=6 if on_tpu else 8, warmup=2,
        )
        prof.save(args.out)
        out["roofline_out"] = args.out
        out["roofline_coverage_pct"] = prof.coverage_pct
        out["roofline_top_segment"] = max(
            (s for s in prof.segments if s.in_step), key=lambda s: s.ms
        ).name
        print(prof.to_markdown(), flush=True)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
