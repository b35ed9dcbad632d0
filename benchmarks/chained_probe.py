"""Measure per-step device time without a host sync per step:
launch K data-dependent steps, fence once on the last loss. Losses are
pulled after timing (device scalars) for the sanity gates.
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


def probe(tag, cfg, B, S, K=20):
    params = llama.init_params(cfg, jax.random.key(0))
    opt = optax.adamw(3e-4)
    state = TrainState.create(params, opt)
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt)
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    for _ in range(2):
        state, m = step(state, batch)
        float(m["loss"])  # fenced warmup
    # chained: no host sync inside the loop
    losses = []
    t0 = time.perf_counter()
    for _ in range(K):
        state, m = step(state, batch)
        losses.append(m["loss"])
    float(losses[-1])  # single fence
    dt = (time.perf_counter() - t0) / K
    # gates after timing: a probe that fails them fails the run
    fl = [float(x) for x in losses]
    assert fl[-1] < fl[0], (fl[0], fl[-1])
    tok_s = B * S / dt
    mfu = tok_s * 3.0 * cfg.flops_per_token(S) / chip_peaks().flops
    print(json.dumps({"tag": tag, "ms_per_step": round(dt * 1e3, 2),
                      "tok_s": round(tok_s), "mfu_pct": round(mfu * 100, 2)}),
          flush=True)


def main():
    open_backend()
    base = llama.LLAMA_400M
    probe("flash_dots_b8", dataclasses.replace(base, attention_impl="flash"), 8, 1024)
    probe("flash_dots_b16", dataclasses.replace(base, attention_impl="flash"), 16, 1024)
    probe("flash_dots_b32", dataclasses.replace(base, attention_impl="flash"), 32, 1024)
    probe("flash_none_b8", dataclasses.replace(base, attention_impl="flash", remat=False), 8, 1024)
    probe("flash_dots_b8_s2048", dataclasses.replace(base, attention_impl="flash"), 8, 2048)
    probe("flash_dots_b4_s4096", dataclasses.replace(base, attention_impl="flash"), 4, 4096)


if __name__ == "__main__":
    main()
