"""Grouped-query attention with a rotary: the llama family's attention
sublayer, the row "gqa" of `llama.MIXERS` (whose `Mixer` says what a
row's module has, this one and models/cca.py, mla.py and dsa.py alike).

The layout (PR 38; CCA since PR 33, MLA since PR 34). q, k and v are
HEAD-MAJOR, [B, heads, S, hd], from where the projections write them (the
weight read as [D, heads, hd], `"bsd,dnh->bnsh"`) to where `wo` contracts
(heads, hd) of what the kernel gives back (`"bhsk,hkd->bsd"`): on a TPU
an array's last two dimensions are its tile, so the tile is (tokens, a
head's channels) and always full, where [B, S, heads, hd] made 8
key-value heads the rows of a half-empty bfloat16 tile. The q/k norm and
the rotary act on the last axis and on major ones (`norm_over_heads`,
nn/layers.py::apply_rope_head_major, whose halves change places on the
MXU so that nothing is cut inside the 128 lanes), the flash kernels,
whose own layout this is, take q, k and v as they are
(ops/attention.attention_head_major), and nn/layers.py::head_major, the
one helper this module and models/cca.py share, pins the tile where the
matmuls write. Under `tp > 1` the rings of parallel/tp_overlap.py hand
back and take [B, S, h] slabs in token order: one `swapaxes` a tensor
after the ring and one before `rs_matmul` stand where the kernel
wrapper's three transposes in and one out stood. models/llama_decode.py
(serving: a cache laid out [.., S, heads, hd]) keeps `apply_rope` and
its own layout.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu.models.llama import stacked_dense
from ray_tpu.nn.layers import apply_rope_head_major, head_major, rope_frequencies
from ray_tpu.ops.attention import attention_head_major

Params = dict[str, Any]


def attention_axes() -> Params:
    """Logical axes of the leaves `attention_params` makes."""
    return {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
    }


def attention_params(config, keys: jax.Array) -> Params:
    """The four projections of `config.n_layers` layers, stacked. `keys`: the
    stack's four attention keys, ONE A MATRIX (the other kinds draw from the first)."""
    c, d, L, dt = config, config.d_model, config.n_layers, config.param_dtype
    q, kv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    return {
        "wq": stacked_dense(keys[0], L, (d, q), dt),
        "wk": stacked_dense(keys[1], L, (d, kv), dt),
        "wv": stacked_dense(keys[2], L, (d, kv), dt),
        "wo": stacked_dense(keys[3], L, (q, d), dt),
    }


def rotary_tables(c) -> dict:
    """What the sublayer reads that is made ONCE, outside the layer scan:
    the rotary's tables over `max_seq` positions (CCA rotates part of a
    head, MLA its decoupled part and DSA two head sizes, from the
    positions themselves, and name none)."""
    with jax.named_scope("attn.rope"):
        cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    return {"cos": cos, "sin": sin}


def norm_over_heads(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """`rms_norm` over the whole projected width of x [B, heads, S, hd]
    (scale [heads * hd]): the mean runs over the head axis and the
    channels, a major axis and the last one, so the tile stays (S, hd)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=(1, 3), keepdims=True)
    scale = scale.astype(jnp.float32).reshape(x.shape[1], 1, x.shape[3])
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def gqa_sublayer(x: jax.Array, lp: Params, c, *, positions: jax.Array,
                 segment_ids: Optional[jax.Array], cos: jax.Array, sin: jax.Array,
                 overlap: bool = False) -> jax.Array:
    """x = RMSNorm(hidden) [B, S, D] -> what the sublayer adds to the
    hidden state, [B, S, D]: full causal attention with rotary, and the
    q/k RMSNorm over the projected width when the configuration has it
    (OLMoE), q, k and v head-major from the projections to `wo` (the
    module's layout paragraph). `overlap`: the two matmul sites gather and
    scatter x inside themselves (llama._block has when)."""
    B, S, D = x.shape
    H, hd, dt = c.n_heads, c.head_dim, x.dtype
    if overlap:
        from ray_tpu.parallel.tp_overlap import ag_matmul, rs_matmul
    with jax.named_scope("attn.qkv"):
        ws = [lp[n].astype(dt) for n in ("wq", "wk", "wv")]
        if overlap:
            # the ring hands back [B, S, h] slabs in token order: one swapaxes each
            # (and no pin: under a mesh the layout is the compiler's, `head_major`)
            q, k, v = (jnp.swapaxes(t.reshape(B, S, -1, hd), 1, 2) for t in ag_matmul(x, ws))
        else:
            q, k, v = (head_major(jnp.einsum("bsd,dnh->bnsh", x, w.reshape(D, -1, hd)))
                       for w in ws)
    with jax.named_scope("attn.rope"):
        if getattr(c, "qk_norm", False):  # over the whole projected width, before rotary
            q = norm_over_heads(q, lp["q_norm"], c.rms_eps)
            k = norm_over_heads(k, lp["k_norm"], c.rms_eps)
        q = apply_rope_head_major(q, cos, sin, positions)
        k = apply_rope_head_major(k, cos, sin, positions)
    with jax.named_scope("attn.attend"):
        o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                 impl=c.attention_impl)
        # named so the "dots" remat policy can SAVE it: the policy recognizes
        # dot_general outputs but not a pallas_call's, so without the name the
        # backward pass re-runs the whole flash kernel forward (~25% of a
        # train step) just to rebuild this tensor
        o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope("attn.out"):
        wo = lp["wo"].astype(dt)
        if overlap:
            return rs_matmul(jnp.swapaxes(o, 1, 2).reshape(B, S, H * hd), wo)
        return jnp.einsum("bhsk,hkd->bsd", o, wo.reshape(H, hd, D))
