"""Multi-head latent attention (MLA) and the GLM-4.7-Flash configuration.

What GLM-4.7-Flash (zai-org/GLM-4.7-Flash, `model_type` glm4_moe_lite: the
DeepSeek-V2/V3 forms, arXiv:2405.04434 and arXiv:2412.19437) adds to the
one decoder of models/llama.py: `GlmLiteConfig` and the attention sublayer
`mla_sublayer` with its parameters and their logical axes, the row "mla"
of `llama.MIXERS`. The block, the layer scan, the head and the loss are
models/llama.py's, and so are the leading DENSE layers before the expert
layers and the multi-token-prediction (MTP) block after them, which any
expert configuration with the fields may have (`llama.init_params` has
the tree). The sigmoid router with its selection bias, the shared expert
and the share of experts held are models/moe.py's.

MLA, with x = RMSNorm(hidden), H heads, `q_lora_rank` r_q, `kv_lora_rank`
r_kv, a head's un-rotated channels d_n, rotary channels d_r, value
channels d_v:

  down    c_q = RMSNorm(x W_qa) [r_q];  [c_kv ; k_rot] = x W_kva
          [r_kv + d_r];  c_kv <- RMSNorm(c_kv);
  up      [q_nope ; q_rot] = c_q W_qb, H heads of d_n + d_r;
          [k_nope ; v] = c_kv W_kvb, H heads of d_n + d_v;
  glue    rotary (all d_r channels, half-split pairing) on every head's
          q_rot and on the ONE k_rot, which all H heads then share:
          q = [q_nope ; q_rot], k = [k_nope ; k_rot];
  attend  causal softmax attention, scale 1 / sqrt(d_n + d_r), through
          ops/attention.attention_head_major: the flash kernel the other
          configurations use, at keys of d_n + d_r and values of d_v
          (256 and 256 here);
  out     hidden += concat(o) W_o  ([H d_v] -> d_model).

Two options, for a model of the family that is not GLM's (Kimi-Linear's
MLA layers, models/kimi_linear.py; both read from the configuration, and
GLM-4.7-Flash's lowered step is what it was without them):
`q_lora_rank` 0 (the published `q_lora_rank` null): the query has NO
latent, `[q_nope ; q_rot] = x W_q` is ONE matrix (the leaf `wq` in place
of `wq_a`, `q_a_norm`, `wq_b`) and no norm; `mla_rope` false (the
published `mla_use_nope` true): NO rotary on q_rot or k_rot, the d_r
channels join the scores as they are projected and the ONE k_rot is
still shared by all heads. d_v need not be d_n + d_r: ops/flash.py's
kernels take values at a width of their own (keys of 192 beside values of
128, the DeepSeek-V3 shape).

This is the form a model is TRAINED in: keys and values are materialised
a head. (Serving would absorb W_kvb into the query and output sides and
cache c_kv and k_rot alone; the engine refuses every expert
configuration, this one by name.) No projection has a bias.

The layout. From the up projections to the kernel every array is
HEAD-MAJOR, [B, H, S, channels] (PERF.md, PR 33: with the heads in a
tile's second-minor dimension CCA's mix ran at five times its
arithmetic): the projections write head-major (the weight read as
[r, H, channels]), k_nope and v come from two matmuls over the two
halves of W_kvb's columns (cutting the small weight, not the
activation), and the kernel takes q, k and v as they are.

Not implemented, and refused by name in models/registry.py: yarn-scaled
rotary (`rope_scaling`), group-limited routing (`n_group` > 1). The
update rule that moves the selection bias between steps is a training
recipe's and is left out: the bias is a parameter that no step moves.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import moe
from ray_tpu.nn.layers import init_dense, rms_norm
from ray_tpu.ops.attention import attention_head_major

Params = dict[str, Any]
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class GlmLiteConfig(moe.MoEConfig):
    """The attention's own sizes and the two kinds of block; the expert
    layer's (router score, scaling, shared expert, experts held) are
    `MoEConfig`'s, since models/moe.py reads them. `d_ff` is the width
    of one routed expert, `n_layers` the dense and expert layers
    together."""

    mixer: ClassVar[str] = "mla"
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    dense_d_ff: int = 10240        # the leading dense layers' SwiGLU
    first_dense_layers: int = 1
    mtp_layers: int = 1            # multi-token-prediction blocks: 0 or 1
    mtp_loss_weight: float = 0.3
    mla_rope: bool = True          # false: no rotary on the d_r channels (`mla_use_nope`)

    @property
    def head_dim(self) -> int:
        """Of a query and a key."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.first_dense_layers

    def _attention_params(self) -> int:
        d, H, rq, rkv = self.d_model, self.n_heads, self.q_lora_rank, self.kv_lora_rank
        return (query_params(self) + d * (rkv + self.qk_rope_head_dim)
                + rkv * H * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * d)

    def _expert_block_matmul_params(self) -> float:
        """Matmul parameters a token meets in one block of the expert-layer
        kind, every expert somewhere: its `top_k` experts and the shared one."""
        d = self.d_model
        return (self._attention_params() + d * self.n_experts
                + 3 * d * (self.top_k * self.d_ff + self.shared_d_ff))

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires in the WHOLE model: 2 per matmul
        parameter it meets plus the causal scores of every attention, the
        MTP block's and its second head pass among them."""
        d = self.d_model
        scores = 2 * (self.head_dim + self.v_head_dim) * self.n_heads * (seq_len + 1) / 2
        blocks = (self.first_dense_layers * (self._attention_params() + 3 * d * self.dense_d_ff)
                  + (self.n_expert_layers + self.mtp_layers) * self._expert_block_matmul_params()
                  + self.mtp_layers * 2 * d * d)
        return (2 * blocks + (self.n_layers + self.mtp_layers) * scores
                + (1 + self.mtp_layers) * 2 * d * self.vocab_size)

    def num_params(self) -> int:
        d, E = self.d_model, self.n_experts
        attn = self._attention_params() + self.q_lora_rank + self.kv_lora_rank + 2 * d
        expert = attn + d * E + E + 3 * d * (self.n_held * self.d_ff + self.shared_d_ff)
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return (self.vocab_size * d + d + head
                + self.first_dense_layers * (attn + 3 * d * self.dense_d_ff)
                + self.n_expert_layers * expert
                + self.mtp_layers * (expert + 2 * d * d + 3 * d))


# zai-org/GLM-4.7-Flash config.json (the catalog's row): one dense layer,
# then 46 of MLA + 64 routed experts of width 1536, 4 a token, + a shared one
GLM_4_7_FLASH = GlmLiteConfig(
    vocab_size=154880, d_model=2048, n_layers=47, n_heads=20, n_kv_heads=20, d_ff=1536,
    max_seq=202752, rope_theta=1e6, rms_eps=1e-5, tie_embeddings=False,
    n_experts=64, top_k=4, norm_topk_prob=True, qk_norm=False,
    router_aux_coeff=0.0, router_z_coeff=0.0, router_score="sigmoid", routed_scaling=1.8,
    shared_d_ff=1536, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, dense_d_ff=10240, first_dense_layers=1,
    mtp_layers=1, mtp_loss_weight=0.3,
)
GLM_LITE_TINY = dataclasses.replace(
    GLM_4_7_FLASH, vocab_size=512, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=32,
    max_seq=128, remat=False, n_experts=8, top_k=2, shared_d_ff=32, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, dense_d_ff=96,
)


def query_params(c) -> int:
    """Matmul parameters of the query's projection: through the latent, or
    ONE matrix where there is none (`q_lora_rank` 0)."""
    wide = c.n_heads * (c.qk_nope_head_dim + c.qk_rope_head_dim)
    return c.d_model * c.q_lora_rank + c.q_lora_rank * wide if c.q_lora_rank else c.d_model * wide


def attention_axes(config=None) -> Params:
    """Logical axes of the leaves `attention_params` makes. The latents
    are narrow and stay whole; the heads divide as the other attentions'.
    `config` (None: a query with a latent) says whether the query has one."""
    if config is not None and not config.q_lora_rank:
        query = {"wq": ("layers", "embed", "heads")}
    else:
        query = {"wq_a": ("layers", "embed", None), "q_a_norm": ("layers", "norm"),
                 "wq_b": ("layers", None, "heads")}
    return {
        **query,
        "wkv_a": ("layers", "embed", None),
        "kv_a_norm": ("layers", "norm"),
        "wkv_b": ("layers", None, "heads"),
        "wo": ("layers", "heads", "embed"),
    }


def attention_params(config: GlmLiteConfig, keys: jax.Array) -> Params:
    """MLA's weights of `config.n_layers` layers, stacked (drawn from `keys[0]`)."""
    c = config
    L, d, H = c.n_layers, c.d_model, c.n_heads
    rq, rkv, dn, dr, dv = (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.v_head_dim)
    keys = jax.random.split(keys[0], 5)

    def per_layer(k, shape):
        return jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype))(jax.random.split(k, L))

    if rq:
        query = {"wq_a": per_layer(keys[0], (d, rq)),
                 "q_a_norm": jnp.ones((L, rq), c.param_dtype),
                 "wq_b": per_layer(keys[1], (rq, H * (dn + dr)))}
    else:  # no latent: one matrix
        query = {"wq": per_layer(keys[0], (d, H * (dn + dr)))}
    return {
        **query,
        "wkv_a": per_layer(keys[2], (d, rkv + dr)),
        "kv_a_norm": jnp.ones((L, rkv), c.param_dtype),
        "wkv_b": per_layer(keys[3], (rkv, H * (dn + dv))),
        "wo": per_layer(keys[4], (H * dv, d)),
    }


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate every channel of x [B, heads, S, d_r] (float32) by position,
    half-split pairing; positions [S] or [B, S]."""
    rot = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=_F32) / rot))
    ang = positions.astype(_F32)[..., None] * inv              # [(B,) S, d_r / 2]
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]    # [B or 1, 1, S, d_r / 2]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _up(c: jax.Array, w: jax.Array) -> jax.Array:
    """An up projection, head-major: c [B, S, r] x w [r, H, k] -> [B, H, S, k].
    The sequence index is spelled as a BATCH dimension of the product (the
    weight repeated over it), which changes nothing on the device and one
    thing under rematerialisation: the "dots" policy of llama._remat saves
    every product WITHOUT batch dimensions, and these three results are
    13,824 of the 22,000 elements a token it would keep of this sublayer,
    for 8.5M of its 21.8M parameters. Kept for all layers they push the
    cell's step past the chip at two sequences (PERF.md, PR 34); recomputed
    from the saved latents they cost one more pass of the up projections."""
    return jnp.einsum("bsr,brhk->bhsk", c, jnp.broadcast_to(w, c.shape[:1] + w.shape))


def mla_sublayer(x: jax.Array, lp: Params, c: GlmLiteConfig, *, positions: jax.Array,
                 segment_ids: Optional[jax.Array]) -> jax.Array:
    """x = RMSNorm(hidden) [B, S, D] -> what the sublayer adds to the
    hidden state, [B, S, D]. The equations and the layout are the
    module's docstring. Named scopes on the device ops, forward and
    backward: `mla.down`, `mla.up`, `mla.glue` (everything that is
    neither a matmul nor the kernel), `mla.attend`, `mla.out`."""
    B, S, D = x.shape
    H, rkv, dt = c.n_heads, c.kv_lora_rank, x.dtype
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    with obs.layer_span("mla.attn"):  # counts engaged sites, while tracing
        with jax.named_scope("mla.down"):
            if c.q_lora_rank:
                c_q = rms_norm(jnp.einsum("bsd,dr->bsr", x, lp["wq_a"].astype(dt)),
                               lp["q_a_norm"], c.rms_eps)
            kv_a = jnp.einsum("bsd,dr->bsr", x, lp["wkv_a"].astype(dt))
            c_kv = rms_norm(kv_a[..., :rkv], lp["kv_a_norm"], c.rms_eps)
        with jax.named_scope("mla.up"):
            if c.q_lora_rank:
                q = _up(c_q, lp["wq_b"].astype(dt).reshape(-1, H, dn + dr))
            else:  # no latent: the query's ONE matrix, written head-major
                q = jnp.einsum("bsd,dhk->bhsk", x, lp["wq"].astype(dt).reshape(D, H, dn + dr))
            w_kv = lp["wkv_b"].astype(dt).reshape(rkv, H, dn + dv)
            k_nope, v = _up(c_kv, w_kv[..., :dn]), _up(c_kv, w_kv[..., dn:])
        with jax.named_scope("mla.glue"):
            if c.mla_rope:
                q_rot = _rope(q[..., dn:].astype(_F32), positions, c.rope_theta)
                q = jnp.concatenate([q[..., :dn], q_rot.astype(dt)], axis=-1)
                k_rot = _rope(kv_a[:, None, :, rkv:].astype(_F32), positions, c.rope_theta)
            else:
                k_rot = kv_a[:, None, :, rkv:]
            # ONE such key a token (rotated or not), shared by all the heads
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rot.astype(dt), (B, H, S, dr))], axis=-1)
        with jax.named_scope("mla.attend"):
            o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                     impl=c.attention_impl)
            # saved by the "dots" remat policy, as models/gqa.py's is
            o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        with jax.named_scope("mla.out"):
            return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(dt).reshape(H, dv, D))
