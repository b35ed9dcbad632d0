"""Chained-fence breakdown of the flagship train step (B=8, S=1024).

Every probe chains `iters` dependent executions and fences ONCE, so the
host dispatch queue stays ahead of the device (see
benchmarks/chained_probe.py). Prints one JSON object.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import llama
from ray_tpu.train.step import TrainState, make_train_step

B = int(os.environ.get("PROF_B", 8))
S = int(os.environ.get("PROF_S", 1024))
ITERS = int(os.environ.get("PROF_ITERS", 20))


def chain(fn, x, iters=ITERS):
    """fn must map x -> x-like (chainable). Fenced once at the end."""
    x = fn(x)
    float(jnp.asarray(jax.tree.leaves(x)[0]).ravel()[0])  # compile+warm
    t0 = time.perf_counter()
    for _ in range(iters):
        x = fn(x)
    float(jnp.asarray(jax.tree.leaves(x)[0]).ravel()[0])
    return (time.perf_counter() - t0) / iters


def probe(name: str):
    """One probe per PROCESS (HBM on the 16G chip can't hold every
    probe's buffers at once; the parent fans out subprocesses)."""
    out = {"B": B, "S": S, "probe": name}
    cfg = dataclasses.replace(llama.LLAMA_400M, attention_impl="flash")
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0,
                                cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    if name == "matmul":
        n = 4096
        a = jnp.ones((n, n), jnp.bfloat16)
        mm = jax.jit(lambda x: (x @ a).astype(jnp.bfloat16))
        dt = chain(mm, a)
        out["matmul4096_tflops"] = round(2 * n**3 / dt / 1e12, 1)

    elif name in ("fwd", "fwd_bwd", "fwd_bwd_noremat"):
        if name == "fwd_bwd_noremat":
            cfg = dataclasses.replace(cfg, remat=False)
        params = llama.init_params(cfg, jax.random.key(0))
        if name == "fwd":
            # caveat: the dependency-forcing tree.map below adds a full
            # params read+write (~GBs of HBM) to every timed iteration —
            # treat fwd/fwd_bwd as UPPER bounds; "step" has no such skew
            fwd = jax.jit(lambda p, b: llama.loss_fn(p, b, cfg))

            def fwd_chain(x):
                l = fwd(x[0], batch)
                p2 = jax.tree.map(lambda t: t + (l * 0).astype(t.dtype), x[0])
                return (p2, l)

            out["ms"] = round(1e3 * chain(jax.jit(fwd_chain), (params, 0.0)), 2)
        else:
            vg = jax.jit(
                lambda p, b: jax.value_and_grad(llama.loss_fn)(p, b, cfg))

            def vg_chain(x):
                l, g = vg(x[0], batch)
                p2 = jax.tree.map(
                    lambda t, gt: t - 0.0 * gt.astype(t.dtype), x[0], g)
                return (p2, l)

            out["ms"] = round(1e3 * chain(jax.jit(vg_chain), (params, 0.0)), 2)

    elif name == "head":
        d, V = cfg.d_model, cfg.vocab_size
        wh = jnp.ones((d, V), jnp.bfloat16)
        tg = jnp.zeros((B * S,), jnp.int32)

        def head_loss(h):
            logits = (h @ wh).astype(jnp.float32)
            lz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tg[:, None], axis=-1)[:, 0]
            return jnp.mean(lz - picked)

        hvg = jax.jit(jax.value_and_grad(head_loss))

        def head_chain(x):
            l, g = hvg(x[0])
            return (x[0] + 0.0 * g, l)

        h = jnp.ones((B * S, d), jnp.bfloat16)
        out["ms"] = round(1e3 * chain(jax.jit(head_chain), (h, 0.0)), 2)

    elif name == "adamw":
        params = llama.init_params(cfg, jax.random.key(0))
        opt = optax.adamw(3e-4)
        opt_state = opt.init(params)
        grads = jax.tree.map(jnp.ones_like, params)

        @jax.jit
        def opt_chain(x):
            p, s = x
            u, s2 = opt.update(grads, s, p)
            return (optax.apply_updates(p, u), s2)

        out["ms"] = round(1e3 * chain(opt_chain, (params, opt_state)), 2)

    elif name == "step":
        opt = optax.adamw(3e-4)
        state = TrainState.create(llama.init_params(cfg, jax.random.key(0)), opt)
        step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt)
        st = step(state, batch)[0]
        st, m = step(st, batch)
        float(m["loss"])  # warm
        t0 = time.perf_counter()
        for _ in range(ITERS):
            st, m = step(st, batch)
        float(m["loss"])
        out["ms"] = round(1e3 * (time.perf_counter() - t0) / ITERS, 2)

    print(json.dumps(out), flush=True)


PROBES = ["matmul", "fwd", "fwd_bwd", "fwd_bwd_noremat", "head", "adamw", "step"]


def main():
    import subprocess
    import sys

    only = os.environ.get("PROF_ONLY")
    if only:
        # the per-probe child opens the backend; the fan-out parent
        # stays off it so each child can have the chip
        from ray_tpu.utils.backend import open_backend

        open_backend()
        probe(only)
        return
    for name in PROBES:
        env = dict(os.environ, PROF_ONLY=name)
        try:
            r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                               env=env, capture_output=True, text=True,
                               timeout=420)
        except subprocess.TimeoutExpired:
            print(json.dumps({"probe": name, "error": "timeout 420s"}),
                  flush=True)
            continue
        line = [l for l in r.stdout.splitlines() if l.startswith("{")]
        if line:
            print(line[-1], flush=True)
        else:
            print(json.dumps({"probe": name, "rc": r.returncode,
                              "error": (r.stderr or "")[-200:]}), flush=True)


if __name__ == "__main__":
    main()
