"""Compressed convolutional attention (CCA) and the ZAYA1 configuration.

What ZAYA1 (Zyphra/ZAYA1-8B; the ZAYA1 report, arXiv:2511.17127) adds to
the one decoder of models/llama.py: `ZayaConfig`, and the attention
sublayer `cca_sublayer` with its parameters and their logical axes. The
block, the layer scan, the head and the loss are models/llama.py's,
which runs `cca_sublayer` as the row "cca" of its `MIXERS`, the kind a
`ZayaConfig` names; the expert layer with its MLP router
and the share of experts held is models/moe.py's.

CCA (arXiv:2510.04476) runs the whole attention in a latent narrower
than the stream. With x = RMSNorm(hidden), H query heads, G key-value
heads of `head_dim`:

  projections   q~ = x W_q [H * hd], k~ = x W_k [G * hd];
                v_t = [x_t W_v1 ; x_{t-1} W_v2]: half of the value
                channels read the PREVIOUS token (the value shift);
  mix           u = [q~ ; k~], H + G heads of hd channels;
                u0 = conv0(u)   depthwise, causal, `conv_kernels[0]` taps;
                u1 = conv1(u0)  a full hd x hd mix inside each head,
                                causal, `conv_kernels[1]` taps;
                m = (q~ + k~ of the head's group) / 2  (the q-k mean);
                q = u1[:H] + m,  k = u1[H:] + mean of m over the group;
                q <- sqrt(hd) q / |q|,  k <- temp_g sqrt(hd) k / |k|
                (a learned temperature per key-value head);
                rotary on the first `rotary_fraction` of each head;
  attend        causal softmax attention in the latent, GQA H / G, scale
                1 / sqrt(hd), through ops/attention.attention_head_major:
                the flash kernel the other configurations use, unchanged;
  out           hidden += o W_o  ([H * hd] -> d_model).

A convolution of k taps is k shifted multiply-adds (depthwise) or one
[S, k hd] x [k hd, hd] matmul a head (grouped: the k shifted operands
side by side in the contraction). The shifted operand is zero where
token t - n belongs to another document (`segment_ids`) or does not
exist: beside the causal mask, the convolutions and the value shift
are what must not leak across a document boundary. Everything that is
neither a matmul nor the kernel is computed in float32.

The layout (PR 33). From the projections to the kernel every array is
HEAD-MAJOR, [B, heads, S, hd] ([B, G, rep, S, hd] where the q-k mean
meets the key-value head's group): on a TPU an array's last two
dimensions are its tile, 8 or 16 rows of 128 lanes, so the tile is
(tokens, a head's channels) and always full. With [B, S, heads, hd]
the 2, 8 or 10 heads were the tile's rows, padded to 8 or 16 (the
values at G = 2 four-fifths padding), a matmul a head came back as
[heads, B, S, hd] to be transposed, and the flash kernel, whose own
layout is [B, H, S, hd], transposed q, k and v again at its door: a
dozen copies a layer under `cca.mix`, which ran at five times its
arithmetic (PERF.md section 6, PR 33). So: the projections write
head-major (the weight read as [d, heads, hd]); q~ and k~ go through
the convolutions apart (what is cut out of a joint [B, H + G, S, hd]
has a padded gradient); the shifts move along axis 2, a row or two of
the tile; means, norms, the temperature and the rotary act on the last
axis or on major ones; `ops/attention.attention_head_major` hands q, k
and v to the kernels as they are, and `cca.out` contracts (H, hd) of
what comes back. XLA chooses the layout of whatever nothing pins, and
for these arrays it chose channels-in-sublanes and back by turns, so
nn/layers.py::head_major, the one helper this module and models/gqa.py
share (its full attention has been head-major the same way since PR 38),
pins the tile where the matmuls write.

What the published config does not fix (no convolution or projection
bias, which key-value head is the shifted one, the form of the q-k
mean, the eps of the L2 norms) is set as ISSUE 32 wrote it down and
listed under `assumed` in chipbench/configs/zaya1-8b-train.json; the
report's learned residual scaling and its update rule for the router's
selection bias are left out (the bias is a parameter that stays zero).
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import moe
from ray_tpu.nn.layers import head_major, init_dense
from ray_tpu.ops.attention import attention_head_major

Params = dict[str, Any]
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ZayaConfig(moe.MoEConfig):
    """The attention's own sizes; the expert layer's (router kind, experts
    held) are `MoEConfig`'s, since models/moe.py reads them. `n_heads`
    / `n_kv_heads` heads of `latent_head_dim` make the latent; `d_ff` is
    the width of one expert."""

    mixer: ClassVar[str] = "cca"
    latent_head_dim: int = 128
    conv_kernels: tuple = (2, 2)     # taps of the depthwise and of the grouped convolution
    rotary_fraction: float = 0.5     # of each head's channels, from the first

    @property
    def head_dim(self) -> int:
        return self.latent_head_dim

    def _attention_params(self) -> int:
        d, hd, H, G = self.d_model, self.head_dim, self.n_heads, self.n_kv_heads
        k0, k1 = self.conv_kernels
        return 2 * d * H * hd + 2 * d * G * hd + (H + G) * hd * (k0 + k1 * hd) + G

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires in the WHOLE model (every
        expert somewhere): 2 per matmul parameter it meets (projections,
        the grouped convolution, the router, its `top_k` experts, the
        head) plus the causal scores in the latent."""
        d, hd, H, G, r = self.d_model, self.head_dim, self.n_heads, self.n_kv_heads, \
            self.router_hidden
        proj = 2 * d * H * hd + 2 * d * G * hd
        conv = (H + G) * hd * hd * self.conv_kernels[1]
        router = d * r + 2 * r * r + r * self.n_experts
        experts = self.top_k * 3 * d * self.d_ff
        scores = 4 * hd * H * (seq_len + 1) / 2
        return (self.n_layers * (2 * (proj + conv + router + experts) + scores)
                + 2 * d * self.vocab_size)

    def num_params(self) -> int:
        d, E, r = self.d_model, self.n_experts, self.router_hidden
        router = d * r + 2 * r * r + r * E + 2 * r + E
        layer = self._attention_params() + router + self.n_held * 3 * d * self.d_ff + 2 * d
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + self.n_layers * layer + d + head


# Zyphra/ZAYA1-8B config.json (the catalog's row): 40 layers alike, each
# CCA then 16 experts of width 2048, one a token, MLP router of width 256
ZAYA1_8B = ZayaConfig(
    vocab_size=262272, d_model=2048, n_layers=40, n_heads=8, n_kv_heads=2, d_ff=2048,
    max_seq=131072, rope_theta=5e6, rms_eps=1e-5, tie_embeddings=True,
    n_experts=16, top_k=1, norm_topk_prob=False, qk_norm=False,
    router_aux_coeff=0.0, router_z_coeff=0.0, router_kind="mlp", router_hidden=256,
    latent_head_dim=128, conv_kernels=(2, 2), rotary_fraction=0.5,
)
ZAYA_TINY = dataclasses.replace(
    ZAYA1_8B, vocab_size=512, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=32,
    max_seq=128, remat=False, n_experts=4, router_hidden=16, latent_head_dim=8,
)


def attention_axes() -> Params:
    """Logical axes of the leaves `attention_params` makes. The value
    halves, the convolutions and the temperature are small and stay whole."""
    return {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv1": ("layers", "embed", None),
        "wv2": ("layers", "embed", None),
        "wo": ("layers", "heads", "embed"),
        "conv0": ("layers", None, None),
        "conv1": ("layers", None, None, None, None),
        "temp": ("layers", None),
    }


def attention_params(config: ZayaConfig, keys: jax.Array) -> Params:
    """CCA's weights of every layer, stacked (drawn from `keys[0]`). A convolution's
    taps are in time order: the LAST tap multiplies the current token."""
    c = config
    L, d, hd, H, G = c.n_layers, c.d_model, c.head_dim, c.n_heads, c.n_kv_heads
    k0, k1 = c.conv_kernels
    keys = jax.random.split(keys[0], 7)

    def per_layer(k, shape, scale=None):
        return jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype, scale))(
            jax.random.split(k, L))

    return {
        "wq": per_layer(keys[0], (d, H * hd)),
        "wk": per_layer(keys[1], (d, G * hd)),
        "wv1": per_layer(keys[2], (d, G * hd // 2)),
        "wv2": per_layer(keys[3], (d, G * hd // 2)),
        "wo": per_layer(keys[4], (H * hd, d)),
        "conv0": per_layer(keys[5], (k0, (H + G) * hd), k0 ** -0.5),
        "conv1": per_layer(keys[6], (k1, H + G, hd, hd), (k1 * hd) ** -0.5),
        "temp": jnp.ones((L, G), c.param_dtype),
    }


def shift_tokens(x: jax.Array, n: int, segment_ids: Optional[jax.Array],
                 axis: int = 1) -> jax.Array:
    """x [B, ...] with the tokens along `axis` -> x at token t - n: zero
    where there is no such token or it belongs to another document."""
    if n == 0:
        return x
    S = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (n, 0)
    y = jax.lax.slice_in_dim(jnp.pad(x, pad), 0, S, axis=axis)
    if segment_ids is None:
        return y
    same = jnp.pad(segment_ids, ((0, 0), (n, 0)), constant_values=-1)[:, :-n] == segment_ids
    along = [1] * x.ndim
    along[0], along[axis] = x.shape[0], S
    return jnp.where(same.reshape(along), y, jnp.zeros((), x.dtype))


def _mix_in_heads(u: jax.Array, w: jax.Array) -> jax.Array:
    """u [B, n, S, c] x w [n, c, d] -> [B, n, S, d] in float32: a matmul
    a head, batched over (B, n) so that the result comes out in the
    operand's own order of dimensions. (Spelled as the dot_general
    itself: the CPU backend refuses the one `jnp.einsum` makes for
    bfloat16 operands and a float32 result.)"""
    w = jnp.broadcast_to(w, u.shape[:1] + w.shape)
    return head_major(jax.lax.dot_general(
        u, w, (((3,), (2,)), ((0, 1), (0, 1))), preferred_element_type=_F32))


def _convolve(u: jax.Array, taps0: jax.Array, taps1: jax.Array,
              segment_ids: Optional[jax.Array]) -> jax.Array:
    """Both causal convolutions over heads u [B, n, S, hd]: depthwise
    taps0 [k0, n, 1, hd] in float32, then the full mix inside each head,
    taps1 [k1, n, hd, hd], as one matmul a head over (tap, channel).
    -> float32. (A shift commutes with the conversion to float32, and is
    made before it: half the bytes.)"""
    k0, k1 = taps0.shape[0], taps1.shape[0]
    n, hd = u.shape[1], u.shape[3]
    taps0 = taps0.astype(_F32)
    u0 = sum(taps0[j] * shift_tokens(u, k0 - 1 - j, segment_ids, axis=2).astype(_F32)
             for j in range(k0)).astype(u.dtype)
    shifted = jnp.concatenate(
        [shift_tokens(u0, k1 - 1 - j, segment_ids, axis=2) for j in range(k1)], axis=-1)
    return _mix_in_heads(shifted, jnp.moveaxis(taps1, 0, 1).reshape(n, k1 * hd, hd).astype(u.dtype))


def _unit_heads(x: jax.Array, eps: float) -> jax.Array:
    """sqrt(hd) x / |x|_2 over the last axis (float32)."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _partial_rope(x: jax.Array, positions: jax.Array, rot: int, theta: float) -> jax.Array:
    """Rotate the first `rot` channels of each head (half-split pairing)
    by position; x [B, ..., S, hd] float32, positions [S] or [B, S]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=_F32) / rot))
    ang = positions.astype(_F32)[..., None] * inv             # [(B,) S, rot / 2]
    if ang.ndim == 2:
        ang = ang[None]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 3) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def cca_sublayer(x: jax.Array, lp: Params, c: ZayaConfig, *, positions: jax.Array,
                 segment_ids: Optional[jax.Array]) -> jax.Array:
    """x = RMSNorm(hidden) [B, S, D] -> what the sublayer adds to the
    hidden state, [B, S, D]. The equations and the layout (head-major
    from the projections to the kernel) are the module's docstring."""
    B, S, D = x.shape
    H, G, hd = c.n_heads, c.n_kv_heads, c.head_dim
    n, rep, dt = H + G, H // G, x.dtype
    k0, k1 = c.conv_kernels
    with obs.layer_span("cca.attn"):  # counts engaged sites, while tracing
        with jax.named_scope("cca.proj"):
            u_q, u_k, v = (
                head_major(jnp.einsum("bsd,dnh->bnsh", x, w.astype(dt).reshape(D, -1, hd)))
                for w in (lp["wq"], lp["wk"], jnp.concatenate([lp["wv1"], lp["wv2"]], axis=1)))
        with jax.named_scope("cca.mix"):
            # the second half of the value CHANNELS (not of the heads) reads token t - 1
            shifted = (jnp.arange(G * hd) >= G * hd // 2).reshape(G, 1, hd)
            v = jnp.where(shifted, shift_tokens(v, 1, segment_ids, axis=2), v)
            taps0 = lp["conv0"].reshape(k0, n, 1, hd)
            q = _convolve(u_q, taps0[:, :H], lp["conv1"][:, :H], segment_ids)
            k = _convolve(u_k, taps0[:, H:], lp["conv1"][:, H:], segment_ids)
            m = 0.5 * (u_q.astype(_F32).reshape(B, G, rep, S, hd) + u_k.astype(_F32)[:, :, None])
            q = q.reshape(B, G, rep, S, hd) + m
            k = k + m.mean(axis=2)
            q = _unit_heads(q, c.rms_eps)
            k = _unit_heads(k, c.rms_eps) * lp["temp"].astype(_F32)[:, None, None]
            rot = int(hd * c.rotary_fraction)
            q = _partial_rope(q, positions, rot, c.rope_theta).reshape(B, H, S, hd).astype(dt)
            k = _partial_rope(k, positions, rot, c.rope_theta).astype(dt)
        with jax.named_scope("cca.attend"):
            o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                     impl=c.attention_impl)
            # saved by the "dots" remat policy, as models/gqa.py's is
            o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        with jax.named_scope("cca.out"):
            return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(dt).reshape(H, hd, D))
