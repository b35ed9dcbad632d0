"""Training batches made on the device: sequences of tokens drawn from
a seeded Zipf(s) unigram over the vocabulary, a fresh batch each step.

The loss can fall on fresh data (toward the unigram entropy), and the
input costs the host nothing. Inverse-CDF sampling (one uniform and one
searchsorted per token), not jax.random.categorical, which would draw
vocab_size random numbers per token.
"""

from __future__ import annotations

import functools
import math

import numpy as np


def unigram(vocab_size: int, zipf_s: float, seed: int) -> np.ndarray:
    """Probability of each token id: Zipf(s) ranks dealt to ids by a
    seeded permutation."""
    w = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** zipf_s
    p = w / w.sum()
    return p[np.random.default_rng([int(seed), 3]).permutation(vocab_size)]


def entropy_nats(p: np.ndarray) -> float:
    return float(-(p * np.log(p)).sum())


def batch_fn(params: dict, vocab_size: int, batch: int, seed: int, sharding=None):
    """-> f(step) = {"tokens": [B, S], "targets": [B, S]} int32 from one jitted program,
    born with `sharding` when one is given."""
    import jax
    import jax.numpy as jnp

    seq = params["seq_len"]
    if seq > params["max_context"]:
        raise ValueError(f"sequence of {seq} tokens is over {params['max_context']}")
    cdf = jnp.asarray(np.cumsum(unigram(vocab_size, params["zipf_s"], seed)), jnp.float32)
    seed32 = jnp.int32(int(seed) % (2 ** 31))
    out = None
    if sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        out = {"tokens": sharding, "targets": sharding}
        cdf, seed32 = jax.device_put((cdf, seed32), NamedSharding(sharding.mesh, PartitionSpec()))

    # the seed's table and key are ARGUMENTS of the program, not constants in it: one
    # program for every seed, so the second run of a checkout loads it from the
    # persistent cache whatever its seed (as constants, every new seed compiled it anew:
    # the one `setup_cache_misses.train` of every ledger line before PR 31)
    def make(cdf, seed32, step):
        key = jax.random.fold_in(jax.random.key(seed32), step)
        u = jax.random.uniform(key, (batch, seq + 1))
        ids = jnp.clip(jnp.searchsorted(cdf, u), 0, vocab_size - 1).astype(jnp.int32)
        return {"tokens": ids[:, :-1], "targets": ids[:, 1:]}

    return functools.partial(jax.jit(make, out_shardings=out), cdf, seed32)


def expected(params: dict, vocab_size: int, seed: int) -> dict:
    p = unigram(vocab_size, params["zipf_s"], seed)
    return {"unigram_entropy_nats": entropy_nats(p), "ln_vocab": math.log(vocab_size)}
