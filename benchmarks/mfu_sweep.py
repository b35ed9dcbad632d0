"""MFU sweep on the local accelerator: remat policy x attention impl x batch.

Prints one JSON line per config. Used to pick the flagship bench config;
not part of the driver bench path. --profile additionally runs the
ray_tpu.profiler ladder per config and appends the segment breakdown to
each line — the sweep then says not just WHICH shape wins but WHERE each
loser's step time goes.
"""

from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import llama
from ray_tpu.profiler.costs import chip_peaks
from ray_tpu.train.step import TrainState, make_train_step
from ray_tpu.utils.backend import open_backend


def bench_config(cfg, B, S, iters=10, tag="", profile=False):
    params = llama.init_params(cfg, jax.random.key(0))
    opt = optax.adamw(3e-4)
    state = TrainState.create(params, opt)
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt)
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    # chained steps, ONE fence at the end: the host keeps dispatching
    # ahead of the device (see bench.py timed_steps)
    for _ in range(2):
        state, m = step(state, batch)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = step(state, batch)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / iters
    tok_s = B * S / dt
    mfu = tok_s * 3.0 * cfg.flops_per_token(S) / chip_peaks().flops
    row = {
        "tag": tag,
        "ms_per_step": round(dt * 1e3, 2),
        "tok_s": round(tok_s, 0),
        "mfu_pct": round(mfu * 100, 2),
    }
    if profile:
        from ray_tpu.profiler import profile_train_step

        prof = profile_train_step(
            cfg, llama.init_params(cfg, jax.random.key(0)), batch, opt,
            iters=5, warmup=2, export_observability=False,
        )
        row["segments_ms"] = {
            s.name: s.ms for s in prof.segments if s.in_step
        }
        row["coverage_pct"] = prof.coverage_pct
    print(json.dumps(row), flush=True)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", action="store_true",
                    help="append per-config segment attribution "
                    "(ray_tpu.profiler) to every row")
    args = ap.parse_args()
    open_backend()

    base = llama.LLAMA_400M
    flash = dataclasses.replace(base, attention_impl="flash",
                                remat_policy="dots", max_seq=8192)
    xla = dataclasses.replace(base, attention_impl="xla",
                              remat_policy="dots", max_seq=8192)
    # sequence scaling is the point of the sweep (round-4 verdict: the
    # flagship number must not be a one-shape trophy) — constant 8k
    # tokens per step across S, plus the flagship B=8/S=1024 row
    configs = [
        ("flash_b8_s1024", flash, 8, 1024),
        ("xla_b8_s1024", xla, 8, 1024),
        ("flash_b16_s1024", flash, 16, 1024),
        ("flash_b8_s2048", flash, 8, 2048),
        ("flash_b4_s2048", flash, 4, 2048),
        ("xla_b4_s2048", xla, 4, 2048),
        ("flash_b2_s4096", flash, 2, 4096),
        ("xla_b2_s4096", xla, 2, 4096),
        ("flash_b1_s8192", flash, 1, 8192),
    ]
    for tag, cfg, B, S in configs:
        bench_config(cfg, B, S, tag=tag, profile=args.profile)


if __name__ == "__main__":
    main()
