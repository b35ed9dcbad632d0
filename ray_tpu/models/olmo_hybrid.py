"""Olmo-Hybrid: gated-delta-rule linear-attention layers and full
attention layers in one stack, over a dense SwiGLU.

What Olmo-Hybrid-7B (allenai/Olmo-Hybrid-7B, `model_type` olmo_hybrid)
adds to the one decoder of models/llama.py: `OlmoHybridConfig`; the
linear-attention sublayer `gdn_sublayer` (the recurrence itself is
ops/gated_delta.py's kernels, forward and backward, and what stands
between the projections and it, ops/gdn_conv.py's); the full-attention
sublayer without a rotary; and
a parameter tree and a layer stack whose blocks differ in KIND. The
head, the loss and the train step are models/llama.py's, which hands
`logical_axes`, `init_params` and the trunk to the module the
configuration names (`stack_module`), as it does for models/laguna.py.

A LINEAR layer's mixer (u the sublayer's input, h one of
`linear_heads` heads, dk = `linear_key_dim`, dv = `linear_value_dim`):

  q~ = u Wq, k~ = u Wk [heads x dk]; v~ = u Wv [heads x dv];
  a causal depthwise convolution of `conv_kernel` taps over time on
  every channel of q~, k~ and v~ (tap j on position t - j: nothing
  ahead of t, zeros before the sequence, no bias), then SiLU;
  q = q~ / |q~| / sqrt(dk), k = k~ / |k~| a head; v = v~ (float32 from
  the projection's output on; ops/gdn_conv.py: the whole chain one
  Pallas kernel a tensor forward and one backward);
  beta = sigmoid(u Wb) a head, doubled under `allow_neg_eigval`;
  g = -exp(A_log) softplus(u Wa + dt_bias) a head, float32;
  S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
  o_t = S_t^T q_t (ops/gated_delta.py: Pallas kernels over chunks of 64,
  the state in VMEM, float32);
  y = RMSNorm_dv(o; one learned [dv] weight) SiLU(u Wg); out = y Wo.

(A published checkpoint's `conv1d.weight` [channels, 1, K] holds tap j at
index K - 1 - j.)

A FULL layer's mixer: q, k, v = u Wq, u Wk, u Wv at `n_heads` heads of
d_model / n_heads, an RMSNorm over the whole projected width of q and
of k (gqa.norm_over_heads), causal softmax attention at
1 / sqrt(head_dim), Wo. NO rotary (`rope_theta` null in the published
config): position reaches a full layer through the linear layers' state.

THE BLOCK norms each sublayer's OUTPUT (the OLMo-2 lineage):
h += RMSNorm(mixer(h)); h += RMSNorm(SwiGLU(h)).

THE LAYOUT is models/llama.py's (PR 38): q, k, v, the gate and o
head-major [B, heads, S, d] from the projections to `wo`.

THE STACK, as models/laguna.py's: the layers are cut into whole PERIODS
of kinds (`laguna.plan` finds the period: linear x 3, full); a
`lax.scan` runs over the periods, a period's blocks unrolled in its
body, each rematerialised by itself. Layers that no whole period holds
are refused by name.

THE TREE. `embed`, `lm_head`, `final_norm`; `layers`: {"period": {"0":
.., "3": ..}} (a period's blocks by position, leaves stacked over the
periods). A linear block's leaves: wq, wk [D, heads x dk], wv, wg
[D, heads x dv], wa, wb [D, heads], conv_q, conv_k [K, heads x dk],
conv_v [K, heads x dv], A_log, dt_bias [heads], o_norm [dv], wo
[heads x dv, D]; a full block's: wq, wk, wv, wo [D, D], q_norm, k_norm
[D]; both: ln1, ln2 [D] and the SwiGLU's w_gate, w_up, w_down.

Trained, not served: the engine refuses the model by name (a recurrent
state beside a key-value cache is not built). Packed documents
(`segment_ids`) are refused by name under a linear layer: a state reset
and a convolution that stops at a boundary are not built.
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
from ray_tpu.models import gqa, laguna, llama
from ray_tpu.nn.layers import head_major, init_dense, rms_norm, swiglu
from ray_tpu.ops.attention import attention_head_major
# by THIS name the benchmark's runner finds the rule the sublayer runs and holds it alone to
# the position-by-position reference (chipbench/runners/train_reference_checked.py): a
# kernel that replaces it is bound to the same name
from ray_tpu.ops.gated_delta import gated_delta_rule
from ray_tpu.ops.gdn_conv import gdn_conv

Params = dict[str, Any]
FULL, LINEAR = "full_attention", "linear_attention"
_F32 = jnp.float32
# saved by llama._remat's "dots" policy beside its own names: what ops/gated_delta.py's forward
# kernel writes (o, the chunks' starting states and their inverses), which is all its backward
# kernel reads beside the inputs: the rule runs twice a layer (forward, backward), not three times
REMAT_SAVES = ("gdn_out", "gdn_states")


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(llama.LlamaConfig):
    """`layer_types` is the PUBLISHED list; a configuration cut in depth
    (`n_layers` smaller) runs its first `n_layers` entries. `n_heads` /
    `n_kv_heads` are the full layers' (equal: no grouping), `d_ff` the
    SwiGLU of every layer."""

    layer_types: tuple = ()
    linear_heads: int = 30        # key heads = value heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    # models/llama.py's seam: the module that builds this tree and runs these layers
    stack_module: str = "ray_tpu.models.olmo_hybrid"
    first_dense_layers = 0        # what laguna.plan reads: no leading layer of another kind

    def kinds(self) -> list:
        """[(type, heads)] of the `n_layers` layers this configuration runs."""
        if len(self.layer_types) < self.n_layers:
            raise ValueError(f"{self.n_layers} layers, but layer_types names "
                             f"{len(self.layer_types)}")
        return [(t, self.linear_heads if t == LINEAR else self.n_heads)
                for t in self.layer_types[:self.n_layers]]

    def _mixer_matmul_params(self, kind: str) -> int:
        d, h = self.d_model, self.linear_heads
        if kind == LINEAR:
            return d * h * (2 * self.linear_key_dim + 3 * self.linear_value_dim + 2)
        return 4 * d * self.n_heads * self.head_dim

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires: 2 per matmul parameter it
        meets; a full layer's scores over the keys before it; a linear
        layer's recurrence in its position-by-position form (k^T S, the
        write k u^T and q^T S: 6 per element of a head's state). The
        convolution's taps, the norms and the gates are elementwise and
        do not count."""
        total = 2.0 * self.d_model * self.vocab_size
        for kind, heads in self.kinds():
            total += 2.0 * (self._mixer_matmul_params(kind) + 3 * self.d_model * self.d_ff)
            if kind == LINEAR:
                total += 6.0 * heads * self.linear_key_dim * self.linear_value_dim
            else:
                total += 4.0 * self.head_dim * heads * (seq_len + 1) / 2
        return total

    def num_params(self) -> int:
        d, h = self.d_model, self.linear_heads
        blocks = 0
        for kind, _ in self.kinds():
            blocks += self._mixer_matmul_params(kind) + 3 * d * self.d_ff + 2 * d
            if kind == LINEAR:
                blocks += (self.conv_kernel * h * (2 * self.linear_key_dim + self.linear_value_dim)
                           + 2 * h + self.linear_value_dim)
            else:
                blocks += 2 * d
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + d + head + blocks


# allenai/Olmo-Hybrid-7B config.json (the catalog's row): (linear x 3, full) x 8
OLMO_HYBRID_7B = OlmoHybridConfig(
    vocab_size=100352, d_model=3840, n_layers=32, n_heads=30, n_kv_heads=30, d_ff=11008,
    max_seq=65536, rope_theta=0.0, rms_eps=1e-6, tie_embeddings=False,
    layer_types=((LINEAR,) * 3 + (FULL,)) * 8,
)
# two periods, small: key heads of 12 and value heads of 24, so neither fills a tile
OLMO_HYBRID_TINY = dataclasses.replace(
    OLMO_HYBRID_7B, vocab_size=512, d_model=64, n_layers=8, n_heads=4, n_kv_heads=4, d_ff=96,
    max_seq=512, remat=False, linear_heads=3, linear_key_dim=12, linear_value_dim=24,
)


# -- the tree ---------------------------------------------------------------------


def _stacked_dense(c: "OlmoHybridConfig", n: int, key: jax.Array, shape: tuple,
                   scale: Optional[float] = None) -> jax.Array:
    """`n` fan-in-initialised matrices of `shape`, stacked."""
    return jax.vmap(lambda k: init_dense(k, shape, c.param_dtype, scale))(jax.random.split(key, n))


def attention_axes(kind: str = LINEAR) -> Params:
    """Logical axes of one kind of mixer's leaves, stacked over the periods."""
    if kind == FULL:
        return {"wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "heads"),
                "wv": ("layers", "embed", "heads"), "wo": ("layers", "heads", "embed"),
                "q_norm": ("layers", "norm"), "k_norm": ("layers", "norm")}
    axes = {n: ("layers", "embed", "heads") for n in ("wq", "wk", "wv", "wg")}
    axes.update({n: ("layers", "embed", None) for n in ("wa", "wb")})
    axes.update({n: ("layers", None, "heads") for n in ("conv_q", "conv_k", "conv_v")})
    axes.update(A_log=("layers", None), dt_bias=("layers", None), o_norm=("layers", "norm"),
                wo=("layers", "heads", "embed"))
    return axes


def attention_params(c: OlmoHybridConfig, key: jax.Array, kind: str = LINEAR, n: int = 1) -> Params:
    """`n` mixers of one kind, leaves stacked over them. The decay's
    `A_log` and `dt_bias` start as fla's GatedDeltaNet starts them: A
    uniform in (0, 16), dt log-uniform in (1e-3, 1e-1) through the
    inverse of softplus."""
    d, h, dk, dv, K = c.d_model, c.linear_heads, c.linear_key_dim, c.linear_value_dim, c.conv_kernel
    keys = jax.random.split(key, 12)
    dense = partial(_stacked_dense, c, n)

    if kind == FULL:
        return {"wq": dense(keys[0], (d, d)), "wk": dense(keys[1], (d, d)),
                "wv": dense(keys[2], (d, d)), "wo": dense(keys[3], (d, d)),
                "q_norm": jnp.ones((n, d), c.param_dtype), "k_norm": jnp.ones((n, d), c.param_dtype)}
    dt = jnp.exp(jax.random.uniform(keys[9], (n, h), _F32) * (math.log(0.1) - math.log(0.001))
                 + math.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "wq": dense(keys[0], (d, h * dk)), "wk": dense(keys[1], (d, h * dk)),
        "wv": dense(keys[2], (d, h * dv)), "wg": dense(keys[3], (d, h * dv)),
        "wa": dense(keys[4], (d, h)), "wb": dense(keys[5], (d, h)),
        # a tap's fan-in is the K positions it sums
        "conv_q": dense(keys[6], (K, h * dk), 1.0 / math.sqrt(K)),
        "conv_k": dense(keys[7], (K, h * dk), 1.0 / math.sqrt(K)),
        "conv_v": dense(keys[8], (K, h * dv), 1.0 / math.sqrt(K)),
        "A_log": jnp.log(jax.random.uniform(keys[10], (n, h), _F32, 1e-3, 16.0)).astype(c.param_dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(c.param_dtype),
        "o_norm": jnp.ones((n, dv), c.param_dtype),
        "wo": dense(keys[11], (h * dv, d)),
    }


def _block_axes(kind: str) -> Params:
    return {"ln1": ("layers", "norm"), **attention_axes(kind), "ln2": ("layers", "norm"),
            "w_gate": ("layers", "embed", "mlp"), "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed")}


def _plan(c: OlmoHybridConfig) -> dict:
    p = laguna.plan(c)
    if p["tail"]:
        raise ValueError(f"{c.n_layers} layers are {p['periods']} whole periods of "
                         f"{len(p['period'])} and {len(p['tail'])} more: a stack that does not "
                         "end on a whole period is not implemented")
    return p


def logical_axes(c: OlmoHybridConfig) -> Params:
    """Of the whole tree `init_params` makes."""
    p = _plan(c)
    axes: Params = {"embed": ("vocab", "embed"), "final_norm": ("norm",),
                    "layers": {"period": {str(j): _block_axes(kind)
                                          for j, (kind, _) in enumerate(p["period"])}}}
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(c: OlmoHybridConfig, key: jax.Array) -> Params:
    """The whole tree (the module's docstring)."""
    p = _plan(c)
    n, d = p["periods"], c.d_model
    k_embed, k_head, k_period = jax.random.split(key, 3)

    def block(j, kind):
        k_mix, k_gate, k_up, k_down = jax.random.split(jax.random.fold_in(k_period, j), 4)
        dense = partial(_stacked_dense, c, n)
        return {"ln1": jnp.ones((n, d), c.param_dtype),
                **attention_params(c, k_mix, kind, n),
                "ln2": jnp.ones((n, d), c.param_dtype),
                "w_gate": dense(k_gate, (d, c.d_ff)), "w_up": dense(k_up, (d, c.d_ff)),
                "w_down": dense(k_down, (c.d_ff, d))}

    params: Params = {
        "embed": init_dense(k_embed, (c.vocab_size, d), c.param_dtype, scale=1.0),
        "layers": {"period": {str(j): block(j, kind) for j, (kind, _) in enumerate(p["period"])}},
        "final_norm": jnp.ones((d,), c.param_dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (d, c.vocab_size), c.param_dtype)
    return params


# -- the sublayers ------------------------------------------------------------------


def gdn_sublayer(x: jax.Array, lp: Params, c: OlmoHybridConfig, *, positions: jax.Array,
                 segment_ids: Optional[jax.Array]) -> jax.Array:
    """The sublayer's input x [B, S, D] -> the linear-attention mixer's
    output [B, S, D] (the module's docstring has the equations;
    `positions` are not read: the state carries the order). Named scopes
    on the device ops, forward and backward: `gdn.proj`, `gdn.conv` (six
    kernels a layer, `gdn_conv_fwd` / `gdn_conv_bwd` for each of q, k and
    v, and the sum of the taps' gradients over a register's sublanes),
    `gdn.gates`, `gdn.scan`, `gdn.norm`, `gdn.out`."""
    if segment_ids is not None:
        raise NotImplementedError(
            "segment_ids (packed documents) under a linear-attention layer: a state reset and "
            "a convolution that stops at a document's boundary are not implemented")
    B, S, D = x.shape
    H, dk, dv, dt = c.linear_heads, c.linear_key_dim, c.linear_value_dim, x.dtype
    with obs.layer_span("gdn.attn"):  # counts engaged sites, while tracing
        with jax.named_scope("gdn.proj"):
            q, k = (head_major(jnp.einsum("bsd,dnh->bnsh", x, lp[n].astype(dt).reshape(D, H, dk)))
                    for n in ("wq", "wk"))
            v, gate = (head_major(jnp.einsum("bsd,dnh->bnsh", x,
                                             lp[n].astype(dt).reshape(D, H, dv)))
                       for n in ("wv", "wg"))
            # float32 out of the matmul: the decay's and beta's logits are not rounded to dt
            a, b = (jnp.einsum("bsd,dh->bhs", x.astype(_F32), lp[n].astype(dt).astype(_F32))
                    for n in ("wa", "wb"))
        with jax.named_scope("gdn.conv"):
            q = gdn_conv(q, lp["conv_q"], scale=dk ** -0.5)
            k = gdn_conv(k, lp["conv_k"], scale=1.0)
            v = gdn_conv(v, lp["conv_v"])
        with jax.named_scope("gdn.gates"):
            beta = jax.nn.sigmoid(b) * (2.0 if c.allow_neg_eigval else 1.0)
            g = (-jnp.exp(lp["A_log"].astype(_F32))[:, None]
                 * jax.nn.softplus(a + lp["dt_bias"].astype(_F32)[:, None]))
        with jax.named_scope("gdn.scan"):
            o = gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("gdn.norm"):
            o = rms_norm(o, lp["o_norm"], c.rms_eps) * jax.nn.silu(gate.astype(_F32))
        with jax.named_scope("gdn.out"):
            return jnp.einsum("bhsk,hkd->bsd", o.astype(dt), lp["wo"].astype(dt).reshape(H, dv, D))


def full_sublayer(x: jax.Array, lp: Params, c: OlmoHybridConfig, *, positions: jax.Array,
                  segment_ids: Optional[jax.Array]) -> jax.Array:
    """x [B, S, D] -> the full-attention mixer's output: llama's GQA
    sublayer with the q/k norm over the whole projected width where the
    rotary would stand, and no rotary. Scopes `attn.qkv`, `attn.rope`
    (the q/k norm), `attn.attend`, `attn.out`."""
    B, S, D = x.shape
    H, hd, dt = c.n_heads, c.head_dim, x.dtype
    with jax.named_scope("attn.qkv"):
        q, k, v = (head_major(jnp.einsum("bsd,dnh->bnsh", x, lp[n].astype(dt).reshape(D, H, hd)))
                   for n in ("wq", "wk", "wv"))
    with jax.named_scope("attn.rope"):
        q = gqa.norm_over_heads(q, lp["q_norm"], c.rms_eps)
        k = gqa.norm_over_heads(k, lp["k_norm"], c.rms_eps)
    with jax.named_scope("attn.attend"):
        o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                 impl=c.attention_impl)
        # saved by the "dots" remat policy, as models/gqa.py's is
        o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope("attn.out"):
        return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(dt).reshape(H, hd, D))


def _block(h: jax.Array, lp: Params, *, c: OlmoHybridConfig, kind: str, positions: jax.Array,
           segment_ids: Optional[jax.Array]) -> jax.Array:
    """One decoder layer of one kind: each sublayer's OUTPUT is normed,
    then added (the reordered norm of the OLMo-2 lineage)."""
    mixer = gdn_sublayer if kind == LINEAR else full_sublayer
    y = mixer(h, lp, c, positions=positions, segment_ids=segment_ids)
    with jax.named_scope("block.norm"):
        h = h + rms_norm(y, lp["ln1"], c.rms_eps)
    with jax.named_scope("dense.ffn"):
        y = swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    with jax.named_scope("block.norm"):
        return h + rms_norm(y, lp["ln2"], c.rms_eps)


def trunk(params: Params, tokens: jax.Array, c: OlmoHybridConfig, *, positions: jax.Array,
          segment_ids: Optional[jax.Array]) -> tuple[jax.Array, None]:
    """The layers, up to the last one's output before the final norm ->
    (h [B, S, D], None: a dense stack has no statistics)."""
    p = _plan(c)
    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens]
    blocks = [llama._remat(partial(_block, c=c, kind=kind, positions=positions,
                                   segment_ids=segment_ids), c) for kind, _ in p["period"]]

    def period(h, lps):
        for j, block in enumerate(blocks):
            h = block(h, lps[str(j)])
        return h, None

    # as models/llama.py's: under this name stand the scan's own slices; every
    # block's operations stand under a scope of their own inside it
    with jax.named_scope("block.stack"):
        h, _ = jax.lax.scan(period, h, params["layers"]["period"])
    return h, None
