"""The plain reference: a dense pre-norm decoder (Mistral-7B-v0.1 / the
llama family) in straightforward jax.numpy.

float32 throughout, `jax.default_matmul_precision("highest")`, no
kernel, no cache, no batching tricks, one sequence at a time. It takes
the program's parameter tree (`embed` [V, D], `layers` with every leaf
stacked over a leading layer axis: ln1, wq, wk, wv, wo, ln2, w_gate,
w_up, w_down, then `final_norm`, `lm_head` [D, V]) and a configuration
file's sizes (HF key names). It imports nothing from ray_tpu.

Follows the published architecture: RMSNorm (eps from the config)
before attention and before the MLP, rotary embeddings on q and k in
the half-split ("rotate_half") layout HF uses, grouped-query attention
with a causal mask, SwiGLU MLP, untied output head. One departure,
stated in every configuration file: no sliding window — v0.1's window
is 4096 and every context here is at most 4096 tokens, where the two
compute the same function; `logits` refuses a longer sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, H, hd] -> rotated by position; half-split pairing."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer(h, lp, shape: dict):
    """One decoder layer on h [S, D]; lp holds this layer's leaves."""
    s = h.shape[0]
    nh, nkv = shape["num_attention_heads"], shape["num_key_value_heads"]
    hd = shape.get("head_dim") or shape["hidden_size"] // nh
    eps = shape["rms_norm_eps"]
    lp = jax.tree.map(lambda w: w.astype(F32), lp)
    x = _rms_norm(h, lp["ln1"], eps)
    q = _rope((x @ lp["wq"]).reshape(s, nh, hd), shape["rope_theta"])
    k = _rope((x @ lp["wk"]).reshape(s, nkv, hd), shape["rope_theta"])
    v = (x @ lp["wv"]).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv                                            # [S, hd] each
        scores = (qh @ kh.T) / jnp.sqrt(F32(hd))
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1) @ vh

    # head by head (lax.map), so that only one [S, S] score matrix is
    # alive at a time: at 4096 tokens all 32 at once are 2 GiB, twice
    heads = jax.lax.map(one_head, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    attn = jnp.swapaxes(heads, 0, 1).reshape(s, nh * hd)
    h = h + attn @ lp["wo"]
    x = _rms_norm(h, lp["ln2"], eps)
    return h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def logits(params, tokens, shape: dict):
    """tokens [S] int32 -> float32 logits [S, V] of the next token at
    every position."""
    if tokens.shape[0] > shape["sliding_window"]:
        raise ValueError(
            f"{tokens.shape[0]} tokens: past the published sliding window of "
            f"{shape['sliding_window']} this reference is not the published model")
    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(F32)[tokens]
        if jax.tree.leaves(params["layers"])[0].shape[0] != shape["num_hidden_layers"]:
            raise ValueError("the parameter tree's depth is not the configuration's")
        # layer after layer over the stacked leaves (scan = a plain loop)
        h, _ = jax.lax.scan(lambda h, lp: (layer(h, lp, shape), None), h, params["layers"])
        h = _rms_norm(h, params["final_norm"].astype(F32), shape["rms_norm_eps"])
        return h @ params["lm_head"].astype(F32)


def sequence_loss(params, tokens, targets, shape: dict):
    """Mean next-token cross-entropy (nats) of one sequence [S]."""
    lg = logits(params, tokens, shape)
    logp = lg - jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    return (-jnp.take_along_axis(logp, targets[:, None], axis=-1)).mean()


def loss(params, tokens, targets, shape: dict):
    """Mean cross-entropy over tokens/targets [B, S] (every sequence as
    long as the others), sequence by sequence."""
    one = jax.jit(lambda p, t, y: sequence_loss(p, t, y, shape))
    return sum(one(params, tokens[b], targets[b]) for b in range(tokens.shape[0])) / tokens.shape[0]
