"""Training batches of PACKED DOCUMENTS made on the device: every
sequence is documents of unlike lengths laid end to end with no padding,
the last one cut at the sequence's end; a fresh batch each step.

A document's length is `clip(round(exp(N(ln median_len, sigma^2))),
min_len, seq_len)`: log-normal, the shape of a web corpus's lengths (at
median 600 and sigma 1.2 the mean is about 1,230 and one document in
twenty is over 4,096, so a sequence of 8,192 holds about seven). Its
tokens are drawn as generators/zipf_tokens.py draws them (that module's
seeded Zipf(s) unigram, one uniform and one searchsorted a token): a
document's content says nothing of where it ends, so what a model must
NOT carry over a boundary is only ever visible in its arithmetic, which
is what the cell's `correct` compares.

A batch is {"tokens", "targets" [B, S] int32, "segment_ids" [B, S] int32
(the document a position lies in, counted from 0 along its sequence),
"mask" [B, S] int32: 0 at a document's LAST position, whose target is
the next document's first token, 1 elsewhere (the sequence's last
position keeps its target when its document is cut there: the token that
would have followed)}. Every position is a token of a document: no
padding, so `train_tok_s` counts them all.

As in that generator the seed's table and key are ARGUMENTS of the one
jitted program: one program for every seed.
"""

from __future__ import annotations

import functools
import math

from chipbench.generators import zipf_tokens


def lengths(key, batch: int, params: dict):
    """[B, seq_len // min_len] int32: the lengths of as many documents as
    could ever be needed (all of them at `min_len` fill the sequence)."""
    import jax
    import jax.numpy as jnp

    seq, lo = params["seq_len"], params["min_len"]
    z = jax.random.normal(key, (batch, -(-seq // lo)))
    drawn = jnp.round(jnp.exp(math.log(params["median_len"]) + params["sigma"] * z))
    return jnp.clip(drawn, lo, seq).astype(jnp.int32)


def documents_of(lens, positions: int):
    """lens [B, n] -> [B, positions] int32: the document position t lies in,
    the documents laid end to end from position 0."""
    import jax
    import jax.numpy as jnp

    ends = jnp.cumsum(lens, axis=1)
    at = jnp.arange(positions, dtype=jnp.int32)
    return jax.vmap(lambda e: jnp.searchsorted(e, at, side="right"))(ends).astype(jnp.int32)


def batch_fn(params: dict, vocab_size: int, batch: int, seed: int, sharding=None):
    """-> f(step) = the batch above from one jitted program, born with
    `sharding` when one is given."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = params["seq_len"]
    if seq > params["max_context"]:
        raise ValueError(f"sequence of {seq} tokens is over {params['max_context']}")
    cdf = jnp.asarray(np.cumsum(zipf_tokens.unigram(vocab_size, params["zipf_s"], seed)),
                      jnp.float32)
    seed32 = jnp.int32(int(seed) % (2 ** 31))
    out = None
    if sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        out = dict.fromkeys(("tokens", "targets", "segment_ids", "mask"), sharding)
        cdf, seed32 = jax.device_put((cdf, seed32), NamedSharding(sharding.mesh, PartitionSpec()))

    def make(cdf, seed32, step):
        k_tokens, k_lengths = jax.random.split(jax.random.fold_in(jax.random.key(seed32), step))
        u = jax.random.uniform(k_tokens, (batch, seq + 1))
        ids = jnp.clip(jnp.searchsorted(cdf, u), 0, vocab_size - 1).astype(jnp.int32)
        doc = documents_of(lengths(k_lengths, batch, params), seq + 1)
        return {"tokens": ids[:, :-1], "targets": ids[:, 1:], "segment_ids": doc[:, :-1],
                "mask": (doc[:, 1:] == doc[:, :-1]).astype(jnp.int32)}

    return functools.partial(jax.jit(make, out_shardings=out), cdf, seed32)


def expected(params: dict, vocab_size: int, seed: int) -> dict:
    """What the other generator expects of the tokens, and of the lengths:
    the log-normal's mean before the clip."""
    return {**zipf_tokens.expected(params, vocab_size, seed),
            "mean_document_unclipped": params["median_len"] * math.exp(params["sigma"] ** 2 / 2)}
