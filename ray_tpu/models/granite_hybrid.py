"""Granite 4.0-H: Mamba-2 mixers and attention layers in one stack, EVERY
layer a mixer AND a SwiGLU, four muP multipliers in the residual path, a
tied table.

What ibm-granite/granite-4.0-h-micro (`model_type` granitemoehybrid, 40
layers at hidden 2048, dense: `num_local_experts` 0) adds to the one
decoder of models/llama.py: `GraniteHybridConfig`, a tree and a stack
built from the PUBLISHED `layer_types` list, the block below. The two
mixers are models/nemotron_h.py's sublayers AS THEY STAND
(`mamba_sublayer`: ops/gdn_conv.py's convolution, ops/ssd.py's scan,
ops/gated_norm.py's norm; `attention_sublayer`: GQA without a rotary),
with the leaves that module's `mamba_params` and `attention_params`
make; the SwiGLU is nn/layers.py's; the head, the loss and the train
step are models/llama.py's, which hands `logical_axes`, `init_params`
and the trunk to the module the configuration names (`stack_module`).

THE EQUATIONS (beside each the reading NOT taken).

  h_0 = `embedding_multiplier` x Emb(t)                               (12)
  layer l, of the kind `layer_types`[l]:
    h += `residual_multiplier` x mixer_l(RMSNorm(h; ln))             (0.22)
    h += `residual_multiplier` x swiglu(RMSNorm(h; ln2))
  logits = RMSNorm(h; final_norm) Emb^T / `logits_scaling`             (8)
  (`tie_word_embeddings`: the table's gradient is the sum of both uses;
  the division stands in the fused cross-entropy's ARGUMENT, h / 8, so no
  logits are stored; NOT taken: a multiplier at 1, each a row of the
  benchmark's one-thing-wrong table). RMSNorm at `rms_eps` 1e-5 with a
  learned scale.

  SwiGLU (`shared_intermediate_size` 8192; no routed experts, no router):
    W_down (silu(u W_gate) . u W_up), no bias. The published leaf is ONE
    input matrix [2 x 8192, 2048]; `w_gate` is its first 8192 rows,
    `w_up` the rest (NOT taken: the halves the other way round, which
    random weights cannot tell apart).

  `mamba`, Mamba-2 (`mamba_heads` 64 x `mamba_head_dim` 64 = 4096 =
  `mamba_expand` x hidden; `ssm_state` 128; `ssm_groups` 1; 4 taps WITH
  a bias; no bias on the projections): models/nemotron_h.py's docstring,
  with B and C [128] shared by ALL 64 heads (ONE group, where that module's
  own model has 8 of 8: NOT taken, and a row of the table) and the gated
  norm over the whole 4,096 channels (one group; the gate BEFORE the norm;
  NOT taken: the norm first). `mamba_chunk_size` 256 is how the published
  kernels cut their sums and no part of the function: `chunk_size` here is
  ops/ssd.py's 128, the side of the MXU's tile, at which a chunk holds
  exactly the two vectors a head that the 64 heads of a group need.

  `attention`: q 32 heads, k and v 8 heads of 64 (hidden / heads), no
  bias, NO rotary (`position_embedding_type` nope; `rope_theta` is read
  by nothing), causal softmax at scale `attention_multiplier` 0.015625
  = 1 / 64 (NOT 64 ** -0.5 = 1 / 8), query head i reads key-value head
  i // 4. The flash kernels scale by head_dim ** -0.5, so the ratio of
  the two, 1 / 8 at the published sizes (a power of two: exact in
  bfloat16), multiplies `wq` before the projection.

PACKED DOCUMENTS (`segment_ids` [B, S]): the mixers stop at a document's
boundary (models/nemotron_h.py's docstring: the convolution's taps, the
scan's state, the flash kernels' mask); the SwiGLU, the norms and the
head read one position each; the batch's `mask` takes a document's last
target out of the loss (models/llama.py::loss_and_weight_fn).

PRECISION and INITIALISATION: models/nemotron_h.py's (parameters float32,
compute bfloat16; convolution, gates, scan, state, gated norm, softmax
and loss float32; the Mamba leaves as the family's). The tied table is
normal x hidden ** -0.5, its scale as the HEAD it also is (fan-in 2048),
so that the first logits have a deviation of 1 / 8 and the first loss of
random weights is ln(vocabulary) + 0.01; at the program's embedding
scale of 1 the first logits would have a deviation of 5.7 and the loss
would start near 25.

THE STACK: models/nemotron_h.py's `segments` of the kinds run: a unit
that repeats is a `lax.scan` over its repetitions. The published 40 are
four periods of ten (5 Mamba, 1 attention, 4 Mamba): one scan of four;
the benchmark's ten are (Mamba x 5), attention, (Mamba x 4). Every block
is rematerialised by itself.

THE TREE. `embed` [V, D] (no `lm_head`), `final_norm`; `layers`: a group
a SEGMENT of the stack, "0", "1", ... in the stack's order, each the
segment's layers of a KIND stacked in their order: "mamba" (`ln`, that
module's mixer leaves, `ln2`, `w_gate`, `w_up` [D, 8192], `w_down` [8192,
D]) and "attention" (`ln`, `wq`, `wk`, `wv`, `wo`, `ln2`, the same three).
By segment, and not one stack a kind as models/nemotron_h.py's, because a
scan then runs over leaves AS THEY STAND: a kind's one stack would be
sliced for each segment that runs some of it and its gradient padded
back and summed, a copy of every Mamba layer's 76M parameters each way
(in the step compiled for a described v5e: 5.5 GiB of temporaries).

NOT IMPLEMENTED, refused by name in models/registry.py: routed experts
(`num_local_experts` > 0: the family's larger members), a rotary
(`position_embedding_type` other than nope), biases, groups that do not
divide the heads; serving (the engine refuses the model by name).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, nemotron_h as nh
from ray_tpu.nn.layers import init_dense, rms_norm, swiglu
# by the name `ssd_scan` the benchmark's runner finds the scan and holds it alone to the
# position-by-position reference (as it does in models/nemotron_h.py)
from ray_tpu.ops.ssd import ssd_scan  # noqa: F401 - read by name

Params = dict[str, Any]
MAMBA, ATTENTION = nh.MAMBA, nh.ATTENTION
KINDS = {"mamba": MAMBA, "attention": ATTENTION}          # a published layer type -> its kind
GROUP = {MAMBA: "mamba", ATTENTION: "attention"}          # a kind -> its group of stacked leaves
REMAT_SAVES = nh.REMAT_SAVES


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(llama.LlamaConfig):
    """`published_types` is the PUBLISHED `layer_types` list, whole; a
    configuration cut in depth (`n_layers` smaller) runs its first
    `n_layers`. `n_heads` / `n_kv_heads` are the attention layers' (a
    head hidden / heads wide), `d_ff` the SwiGLU's width. The Mamba
    mixer's fields carry models/nemotron_h.py's names: that module's
    sublayer and init read them."""

    published_types: tuple = ()
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # a Mamba mixer's output projection is divided by the root of the PUBLISHED depth
    published_layers: int = 40
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    tie_embeddings: bool = True
    # models/llama.py's seam: the module that builds this tree and runs these layers
    stack_module: str = "ray_tpu.models.granite_hybrid"

    @property
    def layer_types(self) -> tuple:
        """The kinds of the `n_layers` layers this configuration runs (by
        this name models/llama.py knows a stack of unlike layers)."""
        if len(self.published_types) < self.n_layers:
            raise ValueError(f"{self.n_layers} layers, but layer_types names "
                             f"{len(self.published_types)}")
        unknown = sorted(set(self.published_types) - set(KINDS))
        if unknown:
            raise NotImplementedError(f"layer types {unknown}: mamba and attention are implemented")
        return tuple(KINDS[t] for t in self.published_types[:self.n_layers])

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    def _matmul_params(self, kind: str) -> int:
        d = self.d_model
        ffn = 3 * d * self.d_ff
        if kind == MAMBA:
            return ffn + d * (self.mamba_inner + self.conv_channels + self.mamba_heads) \
                + self.mamba_inner * d
        return ffn + 2 * d * self.head_dim * (self.n_heads + self.n_kv_heads)

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires: 2 per matmul parameter it meets
        (the tied table once, as the head); an attention layer's scores
        over the keys before it; a Mamba layer's scan position by position,
        5 per element of a head's state (models/nemotron_h.py's count)."""
        total = 2.0 * self.d_model * self.vocab_size
        for kind in self.layer_types:
            total += 2.0 * self._matmul_params(kind)
            if kind == MAMBA:
                total += 5.0 * self.mamba_inner * self.ssm_state
            else:
                total += 4.0 * self.head_dim * self.n_heads * (seq_len + 1) / 2
        return total

    def num_params(self) -> int:
        d = self.d_model
        own = {MAMBA: (self.conv_kernel + 1) * self.conv_channels + 3 * self.mamba_heads
               + self.mamba_inner, ATTENTION: 0}
        blocks = sum(self._matmul_params(kind) + own[kind] + 2 * d for kind in self.layer_types)
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + d + head + blocks


_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
# ibm-granite/granite-4.0-h-micro config.json (the catalog's row)
GRANITE_4_H_MICRO = GraniteHybridConfig(
    vocab_size=100352, d_model=2048, n_layers=40, n_heads=32, n_kv_heads=8, d_ff=8192,
    max_seq=131072, rope_theta=10000.0, rms_eps=1e-5, published_types=_PERIOD * 4,
)
# both kinds and a unit that repeats; 8 heads of 16 in ONE group, a state of 128 (B and C a
# whole head of the convolution's 128 channels each, as published), chunks of 16 (two vectors
# a head); 4 / 2 attention heads of 16, whose scale (1 / 32) is not head_dim ** -0.5
GRANITE_HYBRID_TINY = dataclasses.replace(
    GRANITE_4_H_MICRO, vocab_size=512, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2, d_ff=96,
    max_seq=512, remat=False, mamba_heads=8, mamba_head_dim=16, ssm_state=128, chunk_size=16,
    attention_multiplier=0.03125, published_types=("mamba", "mamba", "attention") * 3,
    published_layers=9,
)


# -- the tree ---------------------------------------------------------------------


def _plan(c: GraniteHybridConfig) -> list:
    """[(unit, n, {kind: how many of it a unit holds})] a segment of the stack."""
    return [(unit, n, {kind: unit.count(kind) for kind in GROUP if kind in unit})
            for unit, n in nh.segments(c.layer_types)]


def logical_axes(c: GraniteHybridConfig) -> Params:
    """Of the whole tree `init_params` makes."""
    of_kind = {k: {**nh._group_axes(c, k), "ln2": ("layers", "norm"), **llama.DENSE_FFN_AXES}
               for k in GROUP}
    layers = {str(i): {GROUP[k]: of_kind[k] for k in per} for i, (_, _, per) in enumerate(_plan(c))}
    axes: Params = {"embed": ("vocab", "embed"), "layers": layers, "final_norm": ("norm",)}
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(c: GraniteHybridConfig, key: jax.Array) -> Params:
    """The whole tree (the module's docstring)."""
    d, pd = c.d_model, c.param_dtype
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    mixers = {MAMBA: nh.mamba_params, ATTENTION: nh.attention_params}

    def stacked(kind, n, key):
        k_mixer, k_gate, k_up, k_down = jax.random.split(key, 4)
        return {"ln": jnp.ones((n, d), pd), **mixers[kind](c, k_mixer, n),
                "ln2": jnp.ones((n, d), pd),
                "w_gate": llama.stacked_dense(k_gate, n, (d, c.d_ff), pd),
                "w_up": llama.stacked_dense(k_up, n, (d, c.d_ff), pd),
                "w_down": llama.stacked_dense(k_down, n, (c.d_ff, d), pd)}

    layers = {str(i): {GROUP[kind]: stacked(kind, n * count,
                                            jax.random.fold_in(jax.random.fold_in(k_layers, i), j))
                       for j, (kind, count) in enumerate(per.items())}
              for i, (_, n, per) in enumerate(_plan(c))}
    params: Params = {
        # the table at the scale of the head it also is (the module's docstring)
        "embed": init_dense(k_embed, (c.vocab_size, d), pd, scale=d ** -0.5),
        "layers": layers,
        "final_norm": jnp.ones((d,), pd),
    }
    if not c.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (d, c.vocab_size), pd)
    return params


# -- the stack ----------------------------------------------------------------------


def _block(h: jax.Array, lp: Params, *, c: GraniteHybridConfig, kind: str,
           segment_ids: Optional[jax.Array]) -> jax.Array:
    """One layer: the mixer of its kind, then the SwiGLU, each on the
    normed stream and added at `residual_multiplier`."""
    with jax.named_scope("block.norm"):
        u = rms_norm(h, lp["ln"], c.rms_eps)
    if kind == MAMBA:
        y = nh.mamba_sublayer(u, lp, c, segment_ids=segment_ids)
    else:
        with jax.named_scope("attn.qkv"):
            # softmax at `attention_multiplier`, where the kernels scale by head_dim ** -0.5
            lp = {**lp, "wq": lp["wq"] * (c.attention_multiplier * c.head_dim ** 0.5)}
        y = nh.attention_sublayer(u, lp, c, segment_ids=segment_ids)
    # the residual add stands in the scope of the sublayer's last matmul, which it fuses into
    with jax.named_scope("ssm.out" if kind == MAMBA else "attn.out"):
        h = h + c.residual_multiplier * y
    with jax.named_scope("block.norm"):
        u = rms_norm(h, lp["ln2"], c.rms_eps)
    with jax.named_scope("dense.ffn"):
        return h + c.residual_multiplier * swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])


def trunk(params: Params, tokens: jax.Array, c: GraniteHybridConfig, *, positions: jax.Array,
          segment_ids: Optional[jax.Array]) -> tuple[jax.Array, None]:
    """The layers, up to the last one's output before the final norm ->
    (h [B, S, D], None: a dense stack has no statistics). `positions` are
    not read: no layer has a rotary, the state carries the order."""
    layers = params["layers"]
    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens] * c.embedding_multiplier
    blocks = {kind: llama._remat(partial(_block, c=c, kind=kind, segment_ids=segment_ids), c)
              for kind in GROUP}
    # as models/llama.py's: under this name stand the scans' own slices; every block's
    # operations stand under a scope of their own inside it
    with jax.named_scope("block.stack"):
        for i, (unit, n, per) in enumerate(_plan(c)):
            # a segment's own leaves as they stand, [repetitions, layers of the kind a unit]
            lps = {kind: jax.tree.map(lambda w: w.reshape((n, per[kind]) + w.shape[1:]),
                                      layers[str(i)][GROUP[kind]]) for kind in per}

            def run_unit(h, lps, unit=unit):
                at = dict.fromkeys(lps, 0)
                for kind in unit:
                    h = blocks[kind](h, jax.tree.map(lambda w: w[at[kind]], lps[kind]))
                    at[kind] += 1
                return h, None

            if n == 1:   # what repeats nowhere: its layers unrolled, no loop of one trip
                h, _ = run_unit(h, jax.tree.map(lambda w: w[0], lps))
            else:
                h, _ = jax.lax.scan(run_unit, h, lps)
    return h, None
