"""Compressed convolutional attention (CCA) and the ZAYA1 configuration.

What ZAYA1 (Zyphra/ZAYA1-8B; the ZAYA1 report, arXiv:2511.17127) adds to
the one decoder of models/llama.py: `ZayaConfig`, and the attention
sublayer `cca_sublayer` with its parameters and their logical axes. The
block, the layer scan, the head and the loss are models/llama.py's,
which calls `cca_sublayer` in place of its own attention when the
configuration is a `ZayaConfig`; the expert layer with its MLP router
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
                1 / sqrt(hd), through ops/attention.attention: the flash
                kernel the other configurations use, unchanged;
  out           hidden += o W_o  ([H * hd] -> d_model).

A convolution of k taps is k shifted multiply-adds (depthwise) or k
[S, hd] x [hd, hd] matmuls a head (grouped). The shifted operand is
zero where token t - n belongs to another document (`segment_ids`) or
does not exist: beside the causal mask, the convolutions and the value
shift are what must not leak across a document boundary. Everything
that is neither a matmul nor the kernel is computed in float32.

What the published config does not fix (no convolution or projection
bias, which key-value head is the shifted one, the form of the q-k
mean, the eps of the L2 norms) is set as ISSUE 32 wrote it down and
listed under `assumed` in chipbench/configs/zaya1-8b-train.json; the
report's learned residual scaling and its update rule for the router's
selection bias are left out (the bias is a parameter that stays zero).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import moe
from ray_tpu.nn.layers import init_dense
from ray_tpu.ops.attention import attention

Params = dict[str, Any]
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class ZayaConfig(moe.MoEConfig):
    """The attention's own sizes; the expert layer's (router kind, experts
    held) are `MoEConfig`'s, since models/moe.py reads them. `n_heads`
    / `n_kv_heads` heads of `latent_head_dim` make the latent; `d_ff` is
    the width of one expert."""

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


def attention_params(config: ZayaConfig, key: jax.Array) -> Params:
    """CCA's weights of every layer, stacked over layers. A convolution's
    taps are in time order: the LAST tap multiplies the current token."""
    c = config
    L, d, hd, H, G = c.n_layers, c.d_model, c.head_dim, c.n_heads, c.n_kv_heads
    k0, k1 = c.conv_kernels
    keys = jax.random.split(key, 7)

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


def shift_tokens(x: jax.Array, n: int, segment_ids: Optional[jax.Array]) -> jax.Array:
    """x [B, S, ...] -> x at token t - n: zero where there is no such
    token or it belongs to another document."""
    if n == 0:
        return x
    tail = ((0, 0),) * (x.ndim - 2)
    y = jnp.pad(x, ((0, 0), (n, 0)) + tail)[:, :-n]
    if segment_ids is None:
        return y
    same = jnp.pad(segment_ids, ((0, 0), (n, 0)), constant_values=-1)[:, :-n] == segment_ids
    return jnp.where(same.reshape(same.shape + (1,) * (x.ndim - 2)), y, jnp.zeros((), x.dtype))


def _mix_in_heads(u: jax.Array, w: jax.Array) -> jax.Array:
    """u [B, S, n, c] x w [n, c, d] -> [B, S, n, d] in float32: a matmul
    a head. (Spelled as the dot_general itself: the CPU backend refuses
    the one `jnp.einsum` makes of "bsnc,ncd->bsnd" for bfloat16 operands
    and a float32 result.)"""
    out = jax.lax.dot_general(u, w, (((3,), (1,)), ((2,), (0,))), preferred_element_type=_F32)
    return jnp.moveaxis(out, 0, 2)


def _unit_heads(x: jax.Array, eps: float) -> jax.Array:
    """sqrt(hd) x / |x|_2 over the last axis (float32)."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _partial_rope(x: jax.Array, positions: jax.Array, rot: int, theta: float) -> jax.Array:
    """Rotate the first `rot` channels of each head (half-split pairing)
    by position; x [B, S, ..., hd] float32, positions [S] or [B, S]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=_F32) / rot))
    ang = positions.astype(_F32)[..., None] * inv             # [(B,) S, rot / 2]
    if ang.ndim == 2:
        ang = ang[None]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def cca_sublayer(x: jax.Array, lp: Params, c: ZayaConfig, *, positions: jax.Array,
                 segment_ids: Optional[jax.Array]) -> jax.Array:
    """x = RMSNorm(hidden) [B, S, D] -> what the sublayer adds to the
    hidden state, [B, S, D]. The equations are the module's docstring."""
    B, S, _ = x.shape
    H, G, hd = c.n_heads, c.n_kv_heads, c.head_dim
    rep, dt = H // G, x.dtype
    k0, k1 = c.conv_kernels
    with obs.layer_span("cca.attn"):  # counts engaged sites, while tracing
        with jax.named_scope("cca.proj"):
            q_lat, k_lat, v_now, v_prev = (
                jnp.einsum("bsd,dh->bsh", x, lp[n].astype(dt)) for n in ("wq", "wk", "wv1", "wv2"))
        with jax.named_scope("cca.mix"):
            v = jnp.concatenate([v_now, shift_tokens(v_prev, 1, segment_ids)], axis=-1)
            v = v.reshape(B, S, G, hd)
            u = jnp.concatenate([q_lat, k_lat], axis=-1).astype(_F32)     # [B, S, (H + G) * hd]
            taps0 = lp["conv0"].astype(_F32)
            u0 = sum(taps0[j] * shift_tokens(u, k0 - 1 - j, segment_ids) for j in range(k0))
            u0 = u0.astype(dt).reshape(B, S, H + G, hd)
            taps1 = lp["conv1"].astype(dt)
            u1 = sum(_mix_in_heads(shift_tokens(u0, k1 - 1 - j, segment_ids), taps1[j])
                     for j in range(k1))
            q_lat = q_lat.astype(_F32).reshape(B, S, G, rep, hd)
            m = 0.5 * (q_lat + k_lat.astype(_F32).reshape(B, S, G, 1, hd))
            q = u1[:, :, :H].reshape(B, S, G, rep, hd) + m
            k = u1[:, :, H:] + m.mean(axis=3)
            q = _unit_heads(q, c.rms_eps)
            k = _unit_heads(k, c.rms_eps) * lp["temp"].astype(_F32)[:, None]
            rot = int(hd * c.rotary_fraction)
            q = _partial_rope(q, positions, rot, c.rope_theta).reshape(B, S, H, hd).astype(dt)
            k = _partial_rope(k, positions, rot, c.rope_theta).astype(dt)
        with jax.named_scope("cca.attend"):
            o = attention(q, k, v, causal=True, segment_ids=segment_ids, impl=c.attention_impl)
            # saved by the "dots" remat policy, as llama._block's is
            o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        with jax.named_scope("cca.out"):
            return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, H * hd), lp["wo"].astype(dt))
