"""The typed stack: sliding-window and full attention layers in one stack,
each kind with its own mask, rotary and (where the model has them) head
count; a headwise output gate or none; an RMSNorm a head on q and k or
none. THREE models run through it and it stays one module (ROADMAP D2):

  * Laguna-S-2.1 (poolside/Laguna-S-2.1, `model_type` laguna): 72 heads
    in a sliding layer and 48 in a full one, the gate, yarn on HALF a head
    of the full layers, a leading dense layer, a shared expert, scaled
    weights;
  * Mellum2-12B-A2.5B (JetBrains/Mellum2-12B-A2.5B-Instruct, `model_type`
    mellum): ONE head count (32 / 4: `heads_per_layer` empty, the period
    found by type alone), no gate (`attn_gate` "none": no `wg` leaf, no
    `*.gate` scope), an RMSNorm a head before the rotary (`qk_head_norm`:
    leaves `q_norm`, `k_norm` [head_dim], scopes `swa.norm` / `attn.norm`),
    yarn over the WHOLE head, no dense layer, no shared expert, unscaled
    renormalised weights. Its "MTP head" (a reader's summary names one;
    the config has no key, size or equation of it) is NOT built;
  * SDAR-30B-A3B (JetLM/SDAR-30B-A3B-Chat, `model_type` sdar_moe): the
    Qwen3-MoE block, every layer FULL attention at 32 / 4 heads of 128
    with the norm a head, no window, no gate, no yarn, 128 experts of
    768, 8 a token; what it adds is no layer but how it is TRAINED, by
    block diffusion (`diffusion_block`: models/block_diffusion.py runs
    this stack over a clean and a noised copy of every sequence, and the
    attention below then runs under ops/flash.py's `blockdiff` mask in
    place of the causal one).

What the stack adds to the one decoder of models/llama.py: `LagunaConfig`;
the attention sublayer `attention_sublayer`; and a parameter tree and a
layer stack whose blocks differ in SHAPE. The head, the loss and the
train step are models/llama.py's, which hands `logical_axes`,
`init_params` and the trunk to this module when the configuration is a
`LagunaConfig`; the expert layer (softmax top-k chosen with a selection
bias, renormalised and scaled weights, a shared expert, a share of the
experts held) is models/moe.py's.

THE LAYER. With x = RMSNorm(hidden), H_l the layer's own head count
(`heads_per_layer`), 8 key-value heads, heads of `head_dim` (explicit:
not d_model / heads), no bias:

  q = x Wq [H_l heads], k = x Wk, v = x Wv [kv heads];
  with `qk_head_norm`: q_h <- RMSNorm(q_h) w_q, k_h <- RMSNorm(k_h) w_k
  over the channels of each head, one learned [head_dim] each, before
  the rotary;
  rotary on q and k by the layer's TYPE (`layer_types`):
    sliding_attention: every channel of a head, inv_freq theta^(-2i/hd);
    full_attention: the FIRST `partial` x hd channels (half-split pairing
      within them), the rest pass through; YaRN's blended frequencies
      (nn/layers.py::yarn_inv_freq) and its attention factor on cos and
      sin. The tables are made from the step's positions, two a step;
  causal softmax attention at 1 / sqrt(hd); a sliding layer's row sees
  the `sliding_window` keys up to its own (ops/flash.py walks only the
  sub-tiles the window meets);
  with `attn_gate` "per-head": g = sigmoid(x Wg) [H_l]: one number a head
  and token, o_h <- g_h o_h (the headwise gate of arXiv:2505.06708,
  after attention, before Wo); with "none" o goes to Wo as it is;
  hidden += concat(o) Wo.

Then the dense SwiGLU (`dense_d_ff`) in the `first_dense_layers` leading
layers, the expert layer in every other.

THE LAYOUT is models/llama.py's (PR 38): q, k, v and o head-major
[B, heads, S, hd] from the projections to `wo`; the rotary of half a
head is one fused pass over full tiles (nn/layers.py::rotate_head_major:
tables padded with cos 1 / sin 0, the swap a block of the permutation);
the gate's multiply stands where `wo` reads o.

THE STACK. Stacked parameters and one `lax.scan` need layers that are
all alike; here wq is [D, 72 x 128] in one layer and [D, 48 x 128] in
the next, with another mask and another table. The layers after the
dense ones are therefore cut into whole PERIODS of (type, heads) (the
shortest that repeats: sliding x 3, full); the scan runs over periods,
a period's blocks unrolled in its body, each with its own kind, and the
layers that no whole period holds (`tail`) run after it, as the dense
ones run before it. Each block is rematerialised by itself.

THE TREE. `embed`, `lm_head`, `final_norm`; `dense_layers` (leaves
stacked over the leading dense layers, which are of one kind);
`layers`: {"router_bias": [expert layers, n_experts] (the selection
biases of EVERY expert block, in layer order: one table, where whoever
balances it writes one array; no gradient reaches it and no step moves
it), "period": {"0": .., "1": ..} (a period's blocks by position, leaves
stacked over the periods), "tail": {"0": ..} (unstacked; absent where
the periods hold every layer)}. A block's leaves: ln1, wq, wk, wv, wg
[D, H_l] (a gated model's), q_norm, k_norm [head_dim] (a normed one's),
wo, ln2 and the dense SwiGLU's or models/moe.py's.

Trained, not served: the engine refuses every expert configuration.
Refused by name in models/registry.py: router logit soft-capping, the
router's weight applied on the input, a gate other than per head or none,
a bias, a dense layer after a sparse one, a rope type other than default
or yarn, a stack that is not periodic.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import llama, moe
from ray_tpu.nn.layers import (
    head_major,
    init_dense,
    rms_norm,
    rope_tables,
    rotate_head_major,
    swiglu,
    yarn_inv_freq,
)
from ray_tpu.ops.attention import attention_head_major

Params = dict[str, Any]
FULL, SLIDING = "full_attention", "sliding_attention"
_F32 = jnp.float32
REMAT_SAVES = ()   # beside llama._remat's own: this stack's kernels are the flash kernels


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One layer type's rotary (`rope_parameters[type]` of the HF config)."""

    theta: float
    partial: float = 1.0          # the share of a head's channels that turn
    rope_type: str = "default"    # or "yarn", with the five below
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def tables(self, head_dim: int, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
        rot = int(head_dim * self.partial)
        if self.rope_type == "yarn":
            inv = yarn_inv_freq(rot, self.theta, self.factor, self.original_max,
                                self.beta_fast, self.beta_slow)
            return rope_tables(positions, inv, self.attention_factor)
        if self.rope_type != "default":
            raise ValueError(f"rope_type {self.rope_type!r}: default or yarn")
        return rope_tables(positions, 1.0 / self.theta ** (jnp.arange(0, rot, 2, dtype=_F32) / rot))


@dataclasses.dataclass(frozen=True)
class LagunaConfig(moe.MoEConfig):
    """`layer_types` and `heads_per_layer` are the PUBLISHED lists; a
    configuration cut in depth (`n_layers` smaller) runs their first
    `n_layers` entries. An empty `heads_per_layer` is `n_heads` in every
    layer. `d_ff` is the width of one routed expert, `n_heads` the
    config's `num_attention_heads` (the full layers')."""

    head_dim: int = 128           # explicit: 3072 / 48 is 64
    layer_types: tuple = ()
    heads_per_layer: tuple = ()
    sliding_window: int = 512
    rope_full: Rotary = Rotary(500000.0)
    rope_sliding: Rotary = Rotary(10000.0)
    attn_gate: str = "per-head"   # or "none"
    qk_head_norm: bool = False    # an RMSNorm a head on q and k, before the rotary
    first_dense_layers: int = 1
    dense_d_ff: int = 12288
    # trained by block diffusion (models/block_diffusion.py) where not 0: the length of a
    # block of positions
    diffusion_block: int = 0
    # models/llama.py's seam: the module that builds this tree and runs these layers
    stack_module: str = "ray_tpu.models.laguna"

    def kinds(self) -> list:
        """[(type, heads)] of the `n_layers` layers this configuration runs."""
        heads = self.heads_per_layer or (self.n_heads,) * len(self.layer_types)
        if not (len(self.layer_types) >= self.n_layers <= len(heads)):
            raise ValueError(f"{self.n_layers} layers, but layer_types / heads_per_layer name "
                             f"{len(self.layer_types)} / {len(heads)}")
        return list(zip(self.layer_types[:self.n_layers], heads[:self.n_layers]))

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.first_dense_layers

    def _block_matmul_params(self, heads: int, dense: bool, experts: int) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * heads + 2 * self.n_kv_heads)
        if self.attn_gate == "per-head":
            attn += d * heads
        if dense:
            return attn + 3 * d * self.dense_d_ff
        return attn + d * self.n_experts + 3 * d * (experts * self.d_ff + self.shared_d_ff)

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires in the WHOLE model, every expert
        somewhere: 2 per matmul parameter it meets, and the scores of each
        layer over the keys its mask leaves a row (all before it, or the
        window's)."""
        total = 2.0 * self.d_model * self.vocab_size
        for l, (kind, heads) in enumerate(self.kinds()):
            keys = (seq_len + 1) / 2
            if kind == SLIDING and seq_len > self.sliding_window:
                w = self.sliding_window  # the first w rows see fewer
                keys = (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len
            total += (2.0 * self._block_matmul_params(heads, l < self.first_dense_layers, self.top_k)
                      + 4.0 * self.head_dim * heads * keys)
        return total

    def num_params(self) -> int:
        d = self.d_model
        norms = 2 * d + (2 * self.head_dim if self.qk_head_norm else 0)
        blocks = sum(self._block_matmul_params(heads, l < self.first_dense_layers, self.n_held)
                     + norms for l, (_, heads) in enumerate(self.kinds()))
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + d + head + blocks + self.n_expert_layers * self.n_experts


_PERIOD = ((FULL, 48),) + ((SLIDING, 72),) * 3
# poolside/Laguna-S-2.1 config.json (the catalog's row): layer 0 full attention + a dense
# SwiGLU of 12288, then 47 layers of 256 routed experts of width 1024, 10 a token, + a shared one
LAGUNA_S_2_1 = LagunaConfig(
    vocab_size=100352, d_model=3072, n_layers=48, n_heads=48, n_kv_heads=8, d_ff=1024,
    max_seq=1048576, rope_theta=500000.0, rms_eps=1e-6, tie_embeddings=False,
    n_experts=256, top_k=10, norm_topk_prob=True, qk_norm=False,
    router_aux_coeff=0.0, router_z_coeff=0.0, router_score="softmax", routed_scaling=2.5,
    shared_d_ff=1024, head_dim=128,
    layer_types=tuple(t for t, _ in _PERIOD * 12), heads_per_layer=tuple(h for _, h in _PERIOD * 12),
    sliding_window=512,
    rope_full=Rotary(500000.0, partial=0.5, rope_type="yarn", factor=128.0, original_max=8192,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=1.4852030263919618),
    rope_sliding=Rotary(10000.0),
    first_dense_layers=1, dense_d_ff=12288,
)
# a dense layer and two periods of four, small: the window shorter than the sequence
_TINY_PERIOD = ((FULL, 4),) + ((SLIDING, 6),) * 3
LAGUNA_TINY = dataclasses.replace(
    LAGUNA_S_2_1, vocab_size=512, d_model=64, n_layers=9, n_heads=4, n_kv_heads=2, d_ff=32,
    max_seq=256, remat=False, n_experts=16, top_k=3, shared_d_ff=32, head_dim=16,
    layer_types=tuple(t for t, _ in _TINY_PERIOD * 3),
    heads_per_layer=tuple(h for _, h in _TINY_PERIOD * 3), sliding_window=24,
    rope_full=dataclasses.replace(LAGUNA_S_2_1.rope_full, original_max=32, factor=8.0),
    dense_d_ff=96,
)
# JetBrains/Mellum2-12B-A2.5B-Instruct config.json (the catalog's row): 28 layers, sliding x 3
# then full, seven times, all at 32 / 4 heads of 128; every layer 64 routed experts of width 896,
# 8 a token, renormalised; `intermediate_size` 7168 is used by no layer
_MELLUM2_PERIOD = (SLIDING,) * 3 + (FULL,)
MELLUM2_12B_A2_5B = LagunaConfig(
    vocab_size=98304, d_model=2304, n_layers=28, n_heads=32, n_kv_heads=4, d_ff=896,
    max_seq=131072, rope_theta=500000.0, rms_eps=1e-6, tie_embeddings=False,
    n_experts=64, top_k=8, norm_topk_prob=True, qk_norm=False,
    router_aux_coeff=0.0, router_z_coeff=0.0, router_score="softmax", routed_scaling=1.0,
    shared_d_ff=0, head_dim=128, layer_types=_MELLUM2_PERIOD * 7, heads_per_layer=(),
    sliding_window=1024,
    rope_full=Rotary(500000.0, rope_type="yarn", factor=16.0, original_max=8192,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782),
    rope_sliding=Rotary(500000.0),
    attn_gate="none", qk_head_norm=True, first_dense_layers=0, dense_d_ff=7168,
)
# two periods of four, small: groups of 4 query heads, the window shorter than the sequence,
# positions past yarn's original length, a ramp over pairs 0-4 of 8 (theta 50: 5e5 would end
# it at pair 1 of so small a head)
MELLUM2_TINY = dataclasses.replace(
    MELLUM2_12B_A2_5B, vocab_size=512, d_model=64, n_layers=8, n_heads=8, n_kv_heads=2, d_ff=32,
    max_seq=256, remat=False, n_experts=16, top_k=4, head_dim=16,
    layer_types=_MELLUM2_PERIOD * 2, sliding_window=24,
    rope_full=dataclasses.replace(MELLUM2_12B_A2_5B.rope_full, theta=50.0, original_max=32,
                                  factor=8.0),
    rope_sliding=Rotary(50.0), dense_d_ff=96,
)
# JetLM/SDAR-30B-A3B-Chat config.json (the catalog's row): 48 layers alike, full attention at
# 32 / 4 heads of 128, 128 routed experts of width 768, 8 a token, renormalised; no window
# (`use_sliding_window` false), no rope scaling; `intermediate_size` 6144 is used by no layer
# (`mlp_only_layers` empty). Blocks of 4 positions: the SDAR release's default, no key of the config
SDAR_30B_A3B = LagunaConfig(
    vocab_size=151936, d_model=2048, n_layers=48, n_heads=32, n_kv_heads=4, d_ff=768,
    max_seq=32768, rope_theta=1000000.0, rms_eps=1e-6, tie_embeddings=False,
    n_experts=128, top_k=8, norm_topk_prob=True, qk_norm=False,
    router_aux_coeff=0.0, router_z_coeff=0.0, router_score="softmax", routed_scaling=1.0,
    shared_d_ff=0, head_dim=128, layer_types=(FULL,) * 48, heads_per_layer=(),
    sliding_window=0, rope_full=Rotary(1000000.0), rope_sliding=Rotary(1000000.0),
    attn_gate="none", qk_head_norm=True, first_dense_layers=0, dense_d_ff=6144,
    diffusion_block=4,
)
# four layers, small: groups of 4 query heads, blocks of 4 in a sequence of 40 (two copies: 80
# rows, no multiple of a tile)
SDAR_TINY = dataclasses.replace(
    SDAR_30B_A3B, vocab_size=512, d_model=64, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=32,
    max_seq=256, remat=False, n_experts=16, top_k=4, head_dim=16, layer_types=(FULL,) * 4,
    rope_full=Rotary(50.0), rope_sliding=Rotary(50.0), dense_d_ff=96,
)


# -- the stack's plan ----------------------------------------------------------


def plan(c: LagunaConfig) -> dict:
    """{"dense": (type, heads) of the leading dense layers, "period":
    [(type, heads)] of one period, "periods": how many whole ones, "tail":
    [(type, heads)] of the layers after the last whole period}."""
    kinds = c.kinds()
    dense, rest = kinds[:c.first_dense_layers], kinds[c.first_dense_layers:]
    if len(set(dense)) > 1:
        raise ValueError(f"the leading dense layers are of unlike kinds: {dense}")
    if not rest:
        raise ValueError("no expert layer after the dense ones")
    p = next(p for p in range(1, len(rest) + 1)
             if all(rest[i] == rest[i + p] for i in range(len(rest) - p)))
    periods = len(rest) // p
    return {"dense": dense[0] if dense else None, "period": rest[:p], "periods": periods,
            "tail": rest[periods * p:]}


def _attention_axes(c: LagunaConfig) -> Params:
    axes = {"wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"), "wo": ("layers", "heads", "embed")}
    if c.attn_gate == "per-head":
        axes["wg"] = ("layers", "embed", "heads")
    if c.qk_head_norm:
        axes.update(q_norm=("layers", "norm"), k_norm=("layers", "norm"))
    return axes


def _block_axes(c: LagunaConfig, dense: bool, stacked: bool = True) -> Params:
    axes = {"ln1": ("layers", "norm"), **_attention_axes(c), "ln2": ("layers", "norm")}
    if dense:
        axes.update(llama.DENSE_FFN_AXES)
    else:
        axes.update(moe.expert_axes(c))
    return axes if stacked else {k: v[1:] for k, v in axes.items()}


def logical_axes(c: LagunaConfig) -> Params:
    """Of the whole tree `init_params` makes."""
    p = plan(c)
    layers: Params = {"router_bias": ("layers", "expert"),
                      "period": {str(j): _block_axes(c, False) for j in range(len(p["period"]))}}
    if p["tail"]:
        layers["tail"] = {str(j): _block_axes(c, False, stacked=False)
                          for j in range(len(p["tail"]))}
    axes: Params = {"embed": ("vocab", "embed"), "layers": layers, "final_norm": ("norm",)}
    if p["dense"] is not None:
        axes["dense_layers"] = _block_axes(c, True)
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _block_params(c: LagunaConfig, key: jax.Array, heads: int, n: int, dense: bool) -> Params:
    """`n` blocks of one kind, leaves stacked over them."""
    d, hd = c.d_model, c.head_dim
    keys = jax.random.split(key, 9)

    def per_layer(k, shape):
        return jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype))(jax.random.split(k, n))

    block = {
        "ln1": jnp.ones((n, d), c.param_dtype),
        "wq": per_layer(keys[0], (d, heads * hd)),
        "wk": per_layer(keys[1], (d, c.n_kv_heads * hd)),
        "wv": per_layer(keys[2], (d, c.n_kv_heads * hd)),
        "wo": per_layer(keys[4], (heads * hd, d)),
        "ln2": jnp.ones((n, d), c.param_dtype),
    }
    if c.attn_gate == "per-head":
        block["wg"] = per_layer(keys[3], (d, heads))
    if c.qk_head_norm:
        block.update({name: jnp.ones((n, hd), c.param_dtype) for name in ("q_norm", "k_norm")})
    if dense:
        block.update(w_gate=per_layer(keys[5], (d, c.dense_d_ff)),
                     w_up=per_layer(keys[6], (d, c.dense_d_ff)),
                     w_down=per_layer(keys[7], (c.dense_d_ff, d)))
    else:
        block.update(moe.expert_params(dataclasses.replace(c, n_layers=n), keys[8]))
    return block


def init_params(c: LagunaConfig, key: jax.Array) -> Params:
    """The whole tree (the module's docstring)."""
    p = plan(c)
    k_embed, k_head, k_dense, k_period, k_tail = jax.random.split(key, 5)
    layers: Params = {
        "router_bias": jnp.zeros((c.n_expert_layers, c.n_experts), c.param_dtype),
        "period": {str(j): _block_params(c, jax.random.fold_in(k_period, j), heads, p["periods"],
                                         dense=False)
                   for j, (_, heads) in enumerate(p["period"])},
    }
    if p["tail"]:
        layers["tail"] = {
            str(j): jax.tree.map(lambda w: w[0], _block_params(
                c, jax.random.fold_in(k_tail, j), heads, 1, dense=False))
            for j, (_, heads) in enumerate(p["tail"])}
    params: Params = {
        "embed": init_dense(k_embed, (c.vocab_size, c.d_model), c.param_dtype, scale=1.0),
        "layers": layers,
        "final_norm": jnp.ones((c.d_model,), c.param_dtype),
    }
    if p["dense"] is not None:
        params["dense_layers"] = _block_params(c, k_dense, p["dense"][1], c.first_dense_layers,
                                               dense=True)
    if not c.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (c.d_model, c.vocab_size), c.param_dtype)
    return params


# -- the block ------------------------------------------------------------------


def attention_sublayer(h: jax.Array, x: jax.Array, lp: Params, c: LagunaConfig, *, kind: str,
                       heads: int, tables: dict, segment_ids: Optional[jax.Array]) -> jax.Array:
    """hidden h and x = RMSNorm(h) [B, S, D] -> h + the sublayer. The
    equations and the layout are the module's docstring. Named scopes on
    the device ops, forward and backward: a full layer's `attn.qkv`,
    `attn.norm` (a normed model's), `attn.rope`, `attn.attend`,
    `attn.gate` (a gated model's), `attn.out`; a sliding layer's `swa.*`,
    so the two kinds' kernels can be told apart."""
    B, S, D = x.shape
    hd, dt = c.head_dim, x.dtype
    sliding = kind == SLIDING
    scope = "swa" if sliding else "attn"
    cos, sin = tables[kind]
    with obs.layer_span("laguna.attn"):  # counts engaged sites, while tracing
        with jax.named_scope(f"{scope}.qkv"):
            q, k, v = (head_major(jnp.einsum("bsd,dnh->bnsh", x,
                                             lp[n].astype(dt).reshape(D, -1, hd)))
                       for n in ("wq", "wk", "wv"))
        if c.qk_head_norm:
            with jax.named_scope(f"{scope}.norm"):
                q, k = rms_norm(q, lp["q_norm"], c.rms_eps), rms_norm(k, lp["k_norm"], c.rms_eps)
        with jax.named_scope(f"{scope}.rope"):
            q, k = rotate_head_major(q, cos, sin), rotate_head_major(k, cos, sin)
        with jax.named_scope(f"{scope}.attend"):
            o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                     impl=c.attention_impl,
                                     window=c.sliding_window if sliding else None,
                                     # trained by block diffusion, the rows are a clean and a
                                     # noised copy of a sequence (a window beside it is refused)
                                     blockdiff=(S // 2, c.diffusion_block)
                                     if c.diffusion_block else None)
            # saved by the "dots" remat policy, as models/gqa.py's is
            o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        if c.attn_gate == "per-head":
            with jax.named_scope(f"{scope}.gate"):
                # one number a head and token; the product stands where `wo` reads o
                g = jax.nn.sigmoid(jnp.einsum("bsd,dh->bhs", x, lp["wg"].astype(dt)).astype(_F32))
                o = (o.astype(_F32) * g[..., None]).astype(dt)
        with jax.named_scope(f"{scope}.out"):
            return h + jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(dt).reshape(heads, hd, D))


def _block(h: jax.Array, lp: Params, *, c: LagunaConfig, kind: str, heads: int, dense: bool,
           tables: dict, segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Optional[Params]]:
    """One decoder layer of one kind -> (h, the expert layer's statistics;
    None for a dense layer). An expert block's `lp` carries its row of
    the selection-bias table as `router_bias`."""
    with jax.named_scope("block.norm"):
        x = rms_norm(h, lp["ln1"], c.rms_eps)
    h = attention_sublayer(h, x, lp, c, kind=kind, heads=heads, tables=tables,
                           segment_ids=segment_ids)
    with jax.named_scope("block.norm"):
        x = rms_norm(h, lp["ln2"], c.rms_eps)
    if dense:
        with jax.named_scope("dense.ffn"):
            return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    y, stats, _ = moe.moe_ffn(x, lp, c)
    with jax.named_scope("moe.combine"):
        return h + y, stats


def trunk(params: Params, tokens: jax.Array, c: LagunaConfig, *, positions: jax.Array,
          segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Params]:
    """The layers, up to the last one's output before the final norm ->
    (h [B, S, D], the expert layers' statistics, leaves stacked over them
    in layer order)."""
    if c.attn_gate not in ("per-head", "none"):
        raise ValueError(f"attention gate {c.attn_gate!r}: per-head or none")
    p = plan(c)
    with jax.named_scope("attn.rope"):
        tables = {FULL: c.rope_full.tables(c.head_dim, positions)}
    with jax.named_scope("swa.rope"):
        tables[SLIDING] = c.rope_sliding.tables(c.head_dim, positions)
    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens]

    def block_of(kind, heads, dense=False):
        return llama._remat(partial(_block, c=c, kind=kind, heads=heads, dense=dense,
                                    tables=tables, segment_ids=segment_ids), c)

    layers = params["layers"]
    bias = layers["router_bias"]
    n, per = p["periods"], len(p["period"])
    # as models/llama.py's: under this name stand the scan's own slices and stacked
    # writes; every block's operations stand under a scope of their own inside it
    with jax.named_scope("block.stack"):
        if p["dense"] is not None:
            h = llama.run_dense_layers(h, params, c.first_dense_layers,
                                       block_of(*p["dense"], dense=True))
        blocks = [block_of(kind, heads) for kind, heads in p["period"]]

        def period(h, xs):
            lps, rows = xs
            stats = []
            for j, block in enumerate(blocks):
                h, s = block(h, {**lps[str(j)], "router_bias": rows[j]})
                stats.append(s)
            return h, jax.tree.map(lambda *a: jnp.stack(a), *stats)

        h, stats = jax.lax.scan(
            period, h, (layers["period"], bias[:n * per].reshape(n, per, -1)))
        stats = jax.tree.map(lambda a: a.reshape((n * per,) + a.shape[2:]), stats)
        for j, (kind, heads) in enumerate(p["tail"]):
            h, s = block_of(kind, heads)(
                h, {**layers["tail"][str(j)], "router_bias": bias[n * per + j]})
            stats = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]), stats, s)
    return h, stats
