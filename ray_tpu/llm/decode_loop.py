"""Multi-step decode: N tokens per host round-trip.

On the single-chip serving path every decode step costs one host sync
(logits down, sampled token back up), and the device idles while the
host samples. The TPU-native fix is to keep the whole
decode-sample-feed loop ON DEVICE: `lax.scan` over `decode_step` with
vectorized sampling between iterations, slots computed from the block
tables in-graph, ONE transfer of [n_steps, B] tokens at the end.

Overshoot semantics: stop conditions (EOS, stop ids, max_tokens) are
evaluated host-side after the chunk; tokens past a stop are discarded
and their KV (which only ever lands in the request's own allocated,
unsealed blocks) is released with the sequence. The reference's vLLM
engine makes the same trade in its multi-step scheduling mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.llm.sampling import sample_tokens
from ray_tpu.models.llama_decode import decode_step


def decode_chunk(
    params,
    tokens: jax.Array,        # [B] current tokens
    positions: jax.Array,     # [B] absolute positions of `tokens`
    block_tables: jax.Array,  # [B, MB]
    context_lens: jax.Array,  # [B] INCLUDING the current token
    cache,
    temperatures: jax.Array,  # [B]
    top_ks: jax.Array,        # [B]
    top_ps: jax.Array,        # [B]
    keys: jax.Array,          # [B] STABLE per-request PRNG keys
    starts: jax.Array,        # [B] absolute output index of step 0's token
    remaining: jax.Array,     # [B] tokens each request can still KEEP
    config,
    *,
    n_steps: int,
    block_size: int,
    trash_slot: int,
    attn_impl: str = "auto",
    sample_mode: str = "full",  # static sampler fast path (llm.sampling)
    lora=None,
):
    """Returns (tokens [n_steps, B], logprobs [n_steps, B], cache).

    Sampling key for step s = fold(request key, starts + s) — a pure
    function of the request and the token's absolute index, so seeded
    requests reproduce regardless of chunk partitioning or batch-mates.
    Steps at/past `remaining` (overshoot the host will discard) write
    the trash page: their KV blocks were never reserved.
    """
    B = tokens.shape[0]
    rows = jnp.arange(B)
    # pad-row mask decided ONCE from the chunk's entry state: inside the
    # scan ctx increments every step, so a `ctx > 0` check would flip a
    # pad row "valid" after the first iteration and its writes (block
    # table row is all zeros) would clobber block 0 — a real sequence's
    # block
    valid = context_lens > 0

    def one_step(carry, s):
        tok, pos, ctx, cache = carry
        # slot for the fed token straight from the block table; padded
        # rows and unreserved overshoot steps write the trash page, NOT
        # block 0
        slot = (
            block_tables[rows, pos // block_size] * block_size
            + pos % block_size
        )
        slot = jnp.where(valid & (s < remaining), slot, trash_slot)
        logits, new_cache = decode_step(
            params, tok, pos, slot, block_tables, ctx, cache, config,
            block_size=block_size, attn_impl=attn_impl, lora=lora,
        )
        step_keys = jax.vmap(jax.random.fold_in)(keys, starts + s)
        next_tok, logprob = sample_tokens(
            logits, temperatures, top_ks, top_ps, step_keys, mode=sample_mode
        )
        return (next_tok, pos + 1, ctx + 1, new_cache), (next_tok, logprob)

    (_, _, _, cache), (toks, logprobs) = jax.lax.scan(
        one_step,
        (tokens, positions, context_lens, cache),
        jnp.arange(n_steps),
    )
    return toks, logprobs, cache
