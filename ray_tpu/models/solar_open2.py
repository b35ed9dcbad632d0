"""Solar-Open2: Kimi-Delta-Attention layers and gated NoPE GQA layers in
one stack, every layer over a sparse expert layer.

What Solar-Open2-250B (upstage/Solar-Open2-250B, `model_type`
solar_open2) adds to the one decoder of models/llama.py:
`SolarOpen2Config`; the linear-attention sublayer `kda_sublayer` (the
recurrence itself is ops/kda.py's chunked rule, two Pallas kernels a
layer that read q, k, v and g where the convolution and the gates wrote
them; what stands between the projections and it, ops/gdn_conv.py's
kernels, Olmo-Hybrid's); the
softmax sublayer `gqa_sublayer` without a rotary and with an elementwise
output gate; and a parameter tree and a layer stack whose blocks differ
in KIND over models/moe.py's expert layer. The head, the loss and the
train step are models/llama.py's, which hands `logical_axes`,
`init_params` and the trunk to the module the configuration names
(`stack_module`), as it does for models/laguna.py and
models/olmo_hybrid.py. What the published config does not say is taken
from the fla library's `KimiDeltaAttention` (arXiv:2510.26692), whose
options `linear_attn_config`, `kda_allow_neg_eigval` and
`kda_use_full_proj` name, and from the sibling `solar_open` / `glm4_moe`
for the block and the router; each reading NOT taken stands beside the
one that is.

THE BLOCK, pre-norm: h += mixer(RMSNorm(h)); h += experts(RMSNorm(h)).
Layer l is a GQA layer where l is in `gqa_layers` (0, 4, 8, ...: one
period is GQA, KDA, KDA, KDA), else a KDA layer.

A KDA mixer (u the normed input, H = `kda_heads` heads, d =
`kda_head_dim` for keys and values alike, r = `kda_rank`):

  q~, k~, v~ = u Wq, u Wk, u Wv [H x d];
  a causal depthwise convolution of `conv_kernel` taps over time on
  every channel of each (tap j on position t - j, zeros before the
  sequence, no bias), then SiLU; q and k L2-normalised a head, q scaled
  by d^-1/2 (float32 from the projection's output on; ops/gdn_conv.py);
  beta = 2 sigmoid(u Wb) a head (`kda_allow_neg_eigval` true,
  `kda_neg_eigval` here: the state's transition may then reflect; false
  is beta = sigmoid(u Wb), Kimi-Linear's, models/kimi_linear.py: the
  kernels read beta as data either way);
  g = -exp(A_log[h]) softplus((u Wf1) Wf2 + dt_bias), a VECTOR of d log
  decays a head and position (Wf1 [D, r], Wf2 [r, H x d]:
  `kda_use_full_proj` false read as this low-rank pair, fla's `f_proj`;
  NOT taken: one full [D, H x d] matrix; A_log [H], dt_bias [H x d]);
  from S = 0,
      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,
      o_t = S_t^T q_t                                    (ops/kda.py, float32);
  y = RMSNorm_d(o; one learned [d] weight) . sigmoid((u Wg1) Wg2 + b)
  (fla's `FusedRMSNormGated` with activation sigmoid; Wg1 [D, r],
  Wg2 [r, H x d], b [H x d]; NOT taken: SiLU, the gated delta rule's);
  out = concat(y) Wo.

A GQA mixer: q [`n_heads` x 128], k, v [`n_kv_heads` x 128] = u Wq, u Wk,
u Wv; NO rotary (`use_rope` false: `rope_theta` and
`partial_rotary_factor` stand in the published config unused; position
reaches a GQA layer through the KDA layers' state) and no q/k norm (the
config names none); causal softmax attention at 128^-1/2; `use_gqa_gate`
(true, the ONE form built; false is refused by name where a
configuration is read) read as Qwen3-Next's ELEMENTWISE gate: gate = u Wg [`n_heads` x 128],
o <- o . sigmoid(gate) before Wo (there one matrix gives the query and
the gate; here they are two leaves of that matrix's halves, `wq` and
`wg`: the same function, and a head's share is then a slice of each;
NOT taken: Laguna's one number a head); out = concat(o) Wo.

THE EXPERT LAYER (models/moe.py): sigmoid scores in float32, a selection
bias added for the choice only, the `top_k` 8 of `n_experts` 320 over ONE
group, the chosen scores renormalised (`norm_topk_prob`) x
`routed_scaling` 1, SwiGLU experts of `d_ff` 1280, plus the shared SwiGLU
of `shared_d_ff` 1280 on every token (NOT taken: softmax scores).

A SHARE. `kda_heads`, `n_heads` and `n_kv_heads` are the heads the
parameters HOLD: a chip of a tensor-parallel group of 8 holds 8 of the
64 KDA heads and 8 / 1 of the 64 / 8 GQA heads (the benchmark's cell),
with the low-rank pairs' first factors (Wf1, Wg1), the norms, the
router and the shared expert whole. What such a chip computes is ITS
heads' part of the mixer's output, `wo` over its rows: a partial sum, as
a row-parallel product gives before its all-reduce; the shares of a
layer add up to the layer (tests/test_solar_open2.py). Nothing here
knows of the other chips, and no code stands in for them.

PRECISION, the rule models/olmo_hybrid.py keeps: parameters float32,
compute bfloat16; the convolution with its SiLU and L2 norms, beta, the
decay's low-rank product from its first factor's output on, every
product of the recurrence, the carried state, the gated norm, the GQA
gate's sigmoid, the router, softmax and the loss float32.

THE LAYOUT is models/llama.py's (PR 38): q, k, v, the gates and o
head-major [B, heads, S, d] from the projections to `wo`.

THE STACK, as models/laguna.py's (`laguna.plan` finds the period): a
`lax.scan` over whole periods, a period's blocks unrolled in its body,
each rematerialised by itself. Layers that no whole period holds are
refused by name.

THE TREE. `embed`, `lm_head`, `final_norm`; `layers`: {"router_bias":
[layers, n_experts] (the selection biases of EVERY expert layer, in
layer order: one table, where whoever balances it writes one array),
"period": {"0": .., "3": ..}} (a period's blocks by position, leaves
stacked over the periods). A KDA block's leaves: wq, wk, wv [D, H x d],
conv_q, conv_k, conv_v [K, H x d], wf1, wg1 [D, r], wf2, wg2 [r, H x d],
wb [D, H], A_log [H], dt_bias, g_bias [H x d], o_norm [d], wo [H x d, D];
a GQA block's: wq, wg [D, heads x 128], wk, wv [D, kv heads x 128], wo;
both: ln1, ln2 [D] and models/moe.py's leaves.

Trained, not served: the engine refuses the model by name (a recurrent
state a head beside a key-value cache is not built, and its experts are
training-only). Packed documents (`segment_ids`) are refused by name
under a KDA layer: a state reset and a convolution that stops at a
boundary are not built.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import laguna, llama, moe
from ray_tpu.nn.layers import head_major, init_dense, rms_norm
from ray_tpu.ops.attention import attention_head_major
from ray_tpu.ops.gdn_conv import gdn_conv
# by THIS name the benchmark's runner finds the rule the sublayer runs and holds it alone to
# the position-by-position reference (chipbench/runners/train_reference_solar_open2.py): a
# kernel that replaces it is bound to the same name
from ray_tpu.ops.kda import kda_rule

Params = dict[str, Any]
GQA, KDA = "gqa", "kda"
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# saved by llama._remat's "dots" policy beside its own names: what ops/kda.py's forward kernel
# writes (o; the chunks' starting states and the pairs' inverses: 32 + 64 + 16 MiB a layer at
# [1, 8, 8192, 128]), so that under a block's `jax.checkpoint` the rule runs twice a layer,
# forward and backward, and no forward again (tests/test_solar_open2_step_compile.py holds the
# step's memory with them kept under the chip's)
REMAT_SAVES = ("kda_out", "kda_states")


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(moe.MoEConfig):
    """`gqa_layers` is the PUBLISHED list, whole; a configuration cut in
    depth (`n_layers` smaller) runs the layers below `n_layers`.
    `n_heads` / `n_kv_heads` / `head_dim` are the GQA layers', `kda_heads`
    / `kda_head_dim` the KDA layers' (the heads HELD: the module's
    docstring), `d_ff` ONE routed expert's width."""

    head_dim: int = 128
    gqa_layers: tuple = ()
    kda_heads: int = 64
    kda_head_dim: int = 128
    kda_rank: int = 128           # the low-rank pairs' inner width (fla: the head's width)
    conv_kernel: int = 4
    kda_neg_eigval: bool = True   # beta doubled (`kda_allow_neg_eigval`)
    # models/llama.py's seam: the module that builds this tree and runs these layers
    stack_module: str = "ray_tpu.models.solar_open2"
    first_dense_layers = 0        # what laguna.plan reads: every layer has experts

    @property
    def layer_types(self) -> tuple:
        """The kinds of the `n_layers` layers this configuration runs (by
        this name models/llama.py knows a stack of unlike layers)."""
        return tuple(GQA if l in self.gqa_layers else KDA for l in range(self.n_layers))

    def kinds(self) -> list:
        """[(type, heads)] of the layers: what `laguna.plan` cuts into periods."""
        return [(t, self.n_heads if t == GQA else self.kda_heads) for t in self.layer_types]

    def _mixer_matmul_params(self, kind: str) -> int:
        d = self.d_model
        if kind == GQA:
            return d * self.head_dim * (3 * self.n_heads + 2 * self.n_kv_heads)
        wide = self.kda_heads * self.kda_head_dim
        return 4 * d * wide + 2 * self.kda_rank * (d + wide) + d * self.kda_heads

    def _expert_matmul_params(self, experts: int) -> int:
        d = self.d_model
        return d * self.n_experts + 3 * d * (experts * self.d_ff + self.shared_d_ff)

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires of the heads HELD, every expert
        somewhere: 2 per matmul parameter it meets; a GQA layer's scores
        over the keys before it; a KDA layer's recurrence in its
        position-by-position form (the decay, k^T S, the write k u^T and
        q^T S: 7 per element of a head's state). The convolution's taps,
        the norms and the gates are elementwise and do not count."""
        total = 2.0 * self.d_model * self.vocab_size
        for kind, heads in self.kinds():
            total += 2.0 * (self._mixer_matmul_params(kind)
                            + self._expert_matmul_params(self.top_k))
            if kind == KDA:
                total += 7.0 * heads * self.kda_head_dim ** 2
            else:
                total += 4.0 * self.head_dim * heads * (seq_len + 1) / 2
        return total

    def num_params(self) -> int:
        d, wide = self.d_model, self.kda_heads * self.kda_head_dim
        own = {KDA: 3 * self.conv_kernel * wide + self.kda_heads + 2 * wide + self.kda_head_dim,
               GQA: 0}
        blocks = sum(self._mixer_matmul_params(kind) + own[kind] + 2 * d
                     + self._expert_matmul_params(self.n_held) + self.n_experts  # + the bias
                     for kind in self.layer_types)
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + d + head + blocks


# upstage/Solar-Open2-250B config.json (the catalog's row): (GQA, KDA x 3) x 12
SOLAR_OPEN2_250B = SolarOpen2Config(
    vocab_size=196608, d_model=4096, n_layers=48, n_heads=64, n_kv_heads=8, d_ff=1280,
    max_seq=1048576, rope_theta=0.0, rms_eps=1e-5, tie_embeddings=False,
    n_experts=320, top_k=8, norm_topk_prob=True, router_aux_coeff=0.0, router_z_coeff=0.0,
    router_score="sigmoid", routed_scaling=1.0, shared_d_ff=1280,
    gqa_layers=tuple(range(0, 48, 4)),
)
# two periods, small: 8 KDA heads of 16, GQA 8 / 2 of 16, 40 experts of 32 (no product fills
# a tile, and the router's 40 columns are no power of two either)
SOLAR_OPEN2_TINY = dataclasses.replace(
    SOLAR_OPEN2_250B, vocab_size=512, d_model=64, n_layers=8, n_heads=8, n_kv_heads=2, d_ff=32,
    max_seq=512, remat=False, n_experts=40, top_k=4, shared_d_ff=32, head_dim=16,
    kda_heads=8, kda_head_dim=16, kda_rank=16, gqa_layers=(0, 4),
)


# -- the tree ---------------------------------------------------------------------


def attention_axes(c: SolarOpen2Config, kind: str = KDA) -> Params:
    """Logical axes of one kind of mixer's leaves, stacked over the periods."""
    if kind == GQA:
        return {"wq": ("layers", "embed", "heads"), "wg": ("layers", "embed", "heads"),
                "wk": ("layers", "embed", "kv_heads"), "wv": ("layers", "embed", "kv_heads"),
                "wo": ("layers", "heads", "embed")}
    axes = {n: ("layers", "embed", "heads") for n in ("wq", "wk", "wv")}
    axes.update({n: ("layers", "embed", None) for n in ("wf1", "wg1", "wb")})
    axes.update({n: ("layers", None, "heads") for n in ("wf2", "wg2", "conv_q", "conv_k",
                                                        "conv_v")})
    axes.update(A_log=("layers", None), dt_bias=("layers", "heads"), g_bias=("layers", "heads"),
                o_norm=("layers", "norm"), wo=("layers", "heads", "embed"))
    return axes


def attention_params(c: SolarOpen2Config, key: jax.Array, kind: str = KDA, n: int = 1) -> Params:
    """`n` mixers of one kind, leaves stacked over them. The decay's
    `A_log` and `dt_bias` start as fla's KimiDeltaAttention starts them:
    A uniform in (1, 16) a head, dt log-uniform in (1e-3, 1e-1) a channel
    through the inverse of softplus; the gate's bias 0."""
    d, hd, pd = c.d_model, c.head_dim, c.param_dtype
    keys = jax.random.split(key, 14)
    dense = lambda k, shape, scale=None: llama.stacked_dense(k, n, shape, pd, scale)  # noqa: E731
    if kind == GQA:
        return {"wq": dense(keys[0], (d, c.n_heads * hd)),
                "wk": dense(keys[1], (d, c.n_kv_heads * hd)),
                "wv": dense(keys[2], (d, c.n_kv_heads * hd)),
                "wo": dense(keys[3], (c.n_heads * hd, d)),
                "wg": dense(keys[4], (d, c.n_heads * hd))}
    H, r, K = c.kda_heads, c.kda_rank, c.conv_kernel
    wide = H * c.kda_head_dim
    dt = jnp.exp(jax.random.uniform(keys[12], (n, wide), _F32) * (math.log(0.1) - math.log(0.001))
                 + math.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "wq": dense(keys[0], (d, wide)), "wk": dense(keys[1], (d, wide)),
        "wv": dense(keys[2], (d, wide)),
        # a tap's fan-in is the K positions it sums
        "conv_q": dense(keys[3], (K, wide), 1.0 / math.sqrt(K)),
        "conv_k": dense(keys[4], (K, wide), 1.0 / math.sqrt(K)),
        "conv_v": dense(keys[5], (K, wide), 1.0 / math.sqrt(K)),
        "wf1": dense(keys[6], (d, r)), "wf2": dense(keys[7], (r, wide)),
        "wg1": dense(keys[8], (d, r)), "wg2": dense(keys[9], (r, wide)),
        "wb": dense(keys[10], (d, H)),
        "A_log": jnp.log(jax.random.uniform(keys[11], (n, H), _F32, 1.0, 16.0)).astype(pd),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "g_bias": jnp.zeros((n, wide), pd),
        "o_norm": jnp.ones((n, c.kda_head_dim), pd),
        "wo": dense(keys[13], (wide, d)),
    }


def _plan(c: SolarOpen2Config) -> dict:
    p = laguna.plan(c)
    if p["tail"]:
        raise ValueError(f"{c.n_layers} layers are {p['periods']} whole periods of "
                         f"{len(p['period'])} and {len(p['tail'])} more: a stack that does not "
                         "end on a whole period is not implemented")
    return p


def _block_axes(c: SolarOpen2Config, kind: str) -> Params:
    experts = moe.expert_axes(c)
    del experts["router_bias"]   # the table beside the periods
    return {"ln1": ("layers", "norm"), **attention_axes(c, kind), "ln2": ("layers", "norm"),
            **experts}


def logical_axes(c: SolarOpen2Config) -> Params:
    """Of the whole tree `init_params` makes."""
    p = _plan(c)
    axes: Params = {"embed": ("vocab", "embed"), "final_norm": ("norm",),
                    "layers": {"router_bias": ("layers", "expert"),
                               "period": {str(j): _block_axes(c, kind)
                                          for j, (kind, _) in enumerate(p["period"])}}}
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(c: SolarOpen2Config, key: jax.Array) -> Params:
    """The whole tree (the module's docstring)."""
    p = _plan(c)
    n, d = p["periods"], c.d_model
    k_embed, k_head, k_period = jax.random.split(key, 3)

    def block(j, kind):
        k_mix, k_experts = jax.random.split(jax.random.fold_in(k_period, j))
        experts = moe.expert_params(dataclasses.replace(c, n_layers=n), k_experts)
        del experts["router_bias"]
        return {"ln1": jnp.ones((n, d), c.param_dtype), **attention_params(c, k_mix, kind, n),
                "ln2": jnp.ones((n, d), c.param_dtype), **experts}

    params: Params = {
        "embed": init_dense(k_embed, (c.vocab_size, d), c.param_dtype, scale=1.0),
        "layers": {"router_bias": jnp.zeros((c.n_layers, c.n_experts), c.param_dtype),
                   "period": {str(j): block(j, kind) for j, (kind, _) in enumerate(p["period"])}},
        "final_norm": jnp.ones((d,), c.param_dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (d, c.vocab_size), c.param_dtype)
    return params


# -- the sublayers ------------------------------------------------------------------


def _low_rank(u: jax.Array, w1: jax.Array, w2: jax.Array, heads: int) -> jax.Array:
    """(u W1) W2 head-major, [B, S, D] -> float32 [B, heads, S, d]: the first
    product in the compute type, float32 out of it and through the second
    (at `highest`: a decay's or a gate's logits are not rounded to bfloat16)."""
    dt = u.dtype
    low = jnp.einsum("bsd,dr->bsr", u, w1.astype(dt), preferred_element_type=_F32)
    w2 = w2.astype(_F32).reshape(w2.shape[0], heads, -1)
    return head_major(jnp.einsum("bsr,rnh->bnsh", low, w2, precision=_HI))


def kda_sublayer(u: jax.Array, lp: Params, c: SolarOpen2Config, *,
                 segment_ids: Optional[jax.Array]) -> jax.Array:
    """The sublayer's input u [B, S, D] -> the KDA mixer's output [B, S,
    D] of the heads held (the module's docstring has the equations).
    Named scopes on the device ops, forward and backward: `kda.proj` (q,
    k, v, beta's logits and both low-rank pairs), `kda.conv` (six kernels
    a layer, `gdn_conv_fwd` / `gdn_conv_bwd` for each of q, k and v),
    `kda.gates` (beta and the log decay), `kda.scan` (ops/kda.py's
    kernels, `kda_fwd` / `kda_bwd`),
    `kda.norm` (the sigmoid-gated norm), `kda.out`."""
    if segment_ids is not None:
        raise NotImplementedError(
            "segment_ids (packed documents) under a KDA linear-attention layer: a state reset "
            "and a convolution that stops at a document's boundary are not implemented")
    D = u.shape[2]
    H, d, dt = c.kda_heads, c.kda_head_dim, u.dtype
    with obs.layer_span("kda.attn"):  # counts engaged sites, while tracing
        with jax.named_scope("kda.proj"):
            q, k, v = (head_major(jnp.einsum("bsd,dnh->bnsh", u, lp[n].astype(dt).reshape(D, H, d)))
                       for n in ("wq", "wk", "wv"))
            # float32 out of the matmul: beta's logits are not rounded to dt
            b = jnp.einsum("bsd,dh->bhs", u.astype(_F32), lp["wb"].astype(dt).astype(_F32))
            f = _low_rank(u, lp["wf1"], lp["wf2"], H)
            gate = _low_rank(u, lp["wg1"], lp["wg2"], H)
        with jax.named_scope("kda.conv"):
            q = gdn_conv(q, lp["conv_q"], scale=d ** -0.5)
            k = gdn_conv(k, lp["conv_k"], scale=1.0)
            v = gdn_conv(v, lp["conv_v"])
        with jax.named_scope("kda.gates"):
            beta = jax.nn.sigmoid(b)
            if c.kda_neg_eigval:
                beta = 2.0 * beta
            g = (-jnp.exp(lp["A_log"].astype(_F32))[:, None, None]
                 * jax.nn.softplus(f + lp["dt_bias"].astype(_F32).reshape(H, 1, d)))
        with jax.named_scope("kda.scan"):
            o = kda_rule(q, k, v, g, beta)
        with jax.named_scope("kda.norm"):
            gate = jax.nn.sigmoid(gate + lp["g_bias"].astype(_F32).reshape(H, 1, d))
            o = rms_norm(o, lp["o_norm"], c.rms_eps) * gate
        with jax.named_scope("kda.out"):
            return jnp.einsum("bhsk,hkd->bsd", o.astype(dt), lp["wo"].astype(dt).reshape(H, d, D))


def gqa_sublayer(u: jax.Array, lp: Params, c: SolarOpen2Config, *,
                 segment_ids: Optional[jax.Array]) -> jax.Array:
    """u [B, S, D] -> the GQA mixer's output of the heads held: no
    rotary, an elementwise sigmoid gate on the attention's output.
    Scopes `attn.qkv` (the gate's projection among them), `attn.attend`,
    `attn.gate`, `attn.out`."""
    D = u.shape[2]
    hd, dt = c.head_dim, u.dtype
    with jax.named_scope("attn.qkv"):
        q, k, v, gate = (head_major(jnp.einsum("bsd,dnh->bnsh", u,
                                               lp[n].astype(dt).reshape(D, -1, hd)))
                         for n in ("wq", "wk", "wv", "wg"))
    with jax.named_scope("attn.attend"):
        o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                 impl=c.attention_impl)
        # saved by the "dots" remat policy, as models/gqa.py's is
        o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope("attn.gate"):
        # the product stands where `wo` reads o
        o = (o.astype(_F32) * jax.nn.sigmoid(gate.astype(_F32))).astype(dt)
    with jax.named_scope("attn.out"):
        return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(dt).reshape(c.n_heads, hd, D))


def _block(h: jax.Array, lp: Params, *, c: SolarOpen2Config, kind: str,
           segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Params]:
    """One decoder layer of one kind -> (h, the expert layer's
    statistics). `lp` carries its row of the selection-bias table as
    `router_bias`."""
    with jax.named_scope("block.norm"):
        u = rms_norm(h, lp["ln1"], c.rms_eps)
    mixer = kda_sublayer if kind == KDA else gqa_sublayer
    y = mixer(u, lp, c, segment_ids=segment_ids)
    # the residual add stands in the scope of the sublayer's last matmul, which it fuses into
    with jax.named_scope("kda.out" if kind == KDA else "attn.out"):
        h = h + y
    with jax.named_scope("block.norm"):
        u = rms_norm(h, lp["ln2"], c.rms_eps)
    y, stats, _ = moe.moe_ffn(u, lp, c)
    with jax.named_scope("moe.combine"):
        return h + y, stats


def trunk(params: Params, tokens: jax.Array, c: SolarOpen2Config, *, positions: jax.Array,
          segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Params]:
    """The layers, up to the last one's output before the final norm ->
    (h [B, S, D], the expert layers' statistics, leaves stacked over them
    in layer order). `positions` are not read: no layer has a rotary, the
    KDA layers' state carries the order."""
    p = _plan(c)
    n, per = p["periods"], len(p["period"])
    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens]
    blocks = [llama._remat(partial(_block, c=c, kind=kind, segment_ids=segment_ids), c)
              for kind, _ in p["period"]]

    def period(h, xs):
        lps, rows = xs
        stats = []
        for j, block in enumerate(blocks):
            h, s = block(h, {**lps[str(j)], "router_bias": rows[j]})
            stats.append(s)
        return h, jax.tree.map(lambda *a: jnp.stack(a), *stats)

    layers = params["layers"]
    # as models/llama.py's: under this name stand the scan's own slices and stacked
    # writes; every block's operations stand under a scope of their own inside it
    with jax.named_scope("block.stack"):
        h, stats = jax.lax.scan(
            period, h, (layers["period"], layers["router_bias"].reshape(n, per, -1)))
    return h, jax.tree.map(lambda a: a.reshape((n * per,) + a.shape[2:]), stats)
