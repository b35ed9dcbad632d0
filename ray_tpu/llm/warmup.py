"""LLMEngine.warmup(): every program of the engine's bucket tables, run
once before traffic.

An engine compiles one program per (class, padded shape, static
argument); under traffic the first request that needs a new one waits
for its compile — minutes cold, a second from the persistent cache. The
tables are bounded by construction (EngineConfig.prefill_buckets(),
decode_buckets(), bt_widths(), pipeline.CHUNK_BUCKETS / STOP_WIDTHS), so
they can be walked. Each dummy dispatch feeds an empty batch: every slot
is the cache's trash page and every row a pad row (context length 0, so
the masked chunk's rows are born done and its loop runs no step); live
pages are never written. The arguments are built by the same helpers the
real dispatches use (DeviceBatchState.build, assemble_batch_arrays), so
the shapes and dtypes cannot drift from them; tests/test_obs_layers.py
holds warmup to "no compile under traffic afterwards".
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu import obs
from ray_tpu.llm import pipeline as pl


def _i32(*shape):
    return jnp.zeros(shape, jnp.int32)


def warm_engine(eng, sample_modes: tuple, stop_widths: tuple) -> dict:
    c = eng.config
    trash = c.num_blocks * c.block_size
    widths, batches = c.bt_widths(), c.decode_buckets()

    def lora(n):
        return eng._lora_arg(np.zeros(n, np.int32))

    def prefill():
        for S in c.prefill_buckets():
            for W in widths:
                _, eng.cache = eng._call(
                    "prefill", (S, W), eng._prefill, eng.params, _i32(1, S),
                    _i32(1, S), jnp.asarray([1], jnp.int32),
                    jnp.full((1, S), trash, jnp.int32), _i32(1, W),
                    jnp.asarray([1], jnp.int32), eng.cache, lora(1))
                yield

    def pipe_chunk():
        for B in batches:
            for W in widths:
                for sw in stop_widths:
                    for mode in sample_modes:
                        state = pl.DeviceBatchState.build(eng, [], (B, W, sw, mode))
                        for n in pl.CHUNK_BUCKETS:
                            eng._run_pipe_chunk(state, n)
                            yield

    def decode():
        for B in batches:
            for W in widths:
                _, eng.cache = eng._call(
                    "decode", (B, W), eng._decode, eng.params, _i32(B), _i32(B),
                    jnp.full((B,), trash, jnp.int32), _i32(B, W), _i32(B),
                    eng.cache, lora(B))
                yield

    def decode_chunk():
        for B in batches:
            for W in widths:
                a, keys = pl.assemble_batch_arrays([], B, W)
                for mode in sample_modes:
                    # _chunk_steps: powers of two from 2 up to decode_chunk
                    for n in (b for b in pl.CHUNK_BUCKETS if 1 < b <= c.decode_chunk):
                        _, _, eng.cache = eng._call(
                            "decode_chunk", (n, mode, B, W),
                            eng._decode_chunk_fn(n, mode), eng.params,
                            jnp.asarray(a["tokens"]), jnp.asarray(a["positions"]),
                            jnp.asarray(a["bt"]), jnp.asarray(a["context_lens"]),
                            eng.cache, jnp.asarray(a["temps"]),
                            jnp.asarray(a["top_ks"]), jnp.asarray(a["top_ps"]),
                            jnp.stack(keys), jnp.asarray(a["starts"]), _i32(B),
                            lora(B))
                        yield

    def mixed():
        from ray_tpu.llm.mixed import token_bucket

        most = token_bucket(c.max_num_seqs * c.mixed_prefill_chunk)
        T = token_bucket(1)
        while T <= most:
            for B in batches:
                for W in widths:
                    _, eng.cache = eng._call(
                        "mixed", (T, (B, W)), eng._mixed_fn, eng.params, _i32(T),
                        _i32(T), jnp.full((T,), trash, jnp.int32), _i32(B, W),
                        _i32(B + 1), _i32(B), eng.cache, lora(T))
                    yield
            T *= 2

    def verify():
        K1 = c.spec.num_draft_tokens + 1
        for B in batches:
            for W in widths:
                _, eng.cache = eng._call(
                    "verify", (K1, (B, W)), eng._verify_fn(K1), eng.params,
                    _i32(B, K1), _i32(B, K1), jnp.full((B, K1), trash, jnp.int32),
                    _i32(B, W), _i32(B), eng.cache, lora(B))
                yield

    # the mixed engine admits through its one ragged program, never _prefill
    classes = {"mixed": mixed} if c.mixed_batch else {"prefill": prefill}
    if c.spec is not None and not c.mixed_batch:
        classes["verify"] = verify  # the ragged verifier re-specializes per packed bucket
    if c.pipeline_decode and c.spec is None:
        classes["pipe_chunk"] = pipe_chunk
    else:
        classes["decode"] = decode
        classes["decode_chunk"] = decode_chunk
    report = {}
    for cls, run in classes.items():
        t0 = time.time()
        with obs.layer_span(f"engine.warmup.{cls}"):
            programs = sum(1 for _ in run())
            jax.block_until_ready(eng.cache)
        how = [e[3] for e in obs.compile_log(since=t0) if "llm_" in e[1]]
        report[cls] = {"programs": programs, "seconds": time.time() - t0,
                       "compiled": how.count("compiled"),
                       "loaded": how.count("loaded")}
    return report
