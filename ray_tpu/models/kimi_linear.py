"""Kimi-Linear: Kimi-Delta-Attention layers and latent-attention (MLA)
layers without a rotary in one stack, a leading dense layer, every other
layer over a sparse expert layer.

What Kimi-Linear-48B-A3B (moonshotai/Kimi-Linear-48B-A3B-Instruct,
`model_type` kimi_linear, arXiv:2510.26692) adds to the one decoder of
models/llama.py: `KimiLinearConfig` and a parameter tree and layer stack
whose blocks differ in KIND (KDA or MLA) and in their feed-forward (the
dense SwiGLU of the leading layers, models/moe.py's expert layer after
them), with a TAIL after the whole periods. Both mixers stand in the
modules that brought them and are run from here as they are:
`solar_open2.kda_sublayer` (ops/kda.py's two Pallas kernels a layer,
ops/gdn_conv.py's for the convolution) with `kda_neg_eigval` false, and
`mla.mla_sublayer` with `q_lora_rank` 0 and `mla_rope` false, over
ops/flash.py at keys of 192 and values of 128. The head, the loss and the
train step are models/llama.py's (`stack_module`, the seam Laguna,
Olmo-Hybrid and Solar-Open2 share). What the published config does not say
is taken from the fla library's `KimiDeltaAttention` and from DeepSeek-V3's
MLA and router (arXiv:2412.19437), whose key names the config carries; each
reading NOT taken stands beside the one that is.

THE EQUATIONS. Layers are numbered from 1, as `linear_attn_config` numbers
them. Pre-norm block: h += mixer(RMSNorm(h)); h += ffn(RMSNorm(h)); a final
RMSNorm; an untied head. Layer l's mixer is MLA where l is in `mla_layers`
(the published `full_attn_layers`: 4, 8, .. 24, 27), else KDA (the published
`kda_layers` are every other layer). Layer 1's ffn is a dense SwiGLU of
`dense_d_ff` 9216 (`first_k_dense_replace` 1); layers 2-27 are expert layers
(`moe_layer_freq` 1).

  KDA (H 32 heads, d 128 for keys and values alike, rank 128, 4 taps):
  exactly models/solar_open2.py's (its docstring has every line: q~, k~, v~
  through a causal depthwise convolution and SiLU, q and k L2-normalised,
  g = -exp(A_log) softplus((u Wf1) Wf2 + dt_bias) a VECTOR of d log decays,
  the delta rule, the sigmoid-gated RMSNorm, Wo) with ONE difference:
      beta = sigmoid(u Wb), NOT doubled
  (the row's `config` has no key that asks for negative eigenvalues; fla's
  default `allow_neg_eigval` is false). NOT taken: beta doubled (Solar's).

  MLA, NoPE (u the normed input): q = u Wq, 32 heads of 128 + 64
  (`q_lora_rank` null: no down projection, no norm); [c_kv ; k_r] = u W_kva
  [512 + 64]; c_kv <- RMSNorm(c_kv); [k_n ; v] = c_kv W_kvb, 32 heads of
  128 + 128; k = [k_n ; k_r] with the ONE k_r shared by all heads; NO rotary
  on q's last 64 channels or on k_r (`mla_use_nope` true: `rope_theta`
  stands in the published file, read by nothing; position reaches the layer
  through the KDA layers' state, as in Solar's GQA layer); causal softmax at
  scale 192^-1/2; out = concat(o) Wo [32 x 128 -> 2304]; no bias, no output
  gate (the config names none). NOT taken: a rotary on the 64 channels;
  scale 128^-1/2.

  Experts (models/moe.py): sigmoid scores over 256 in float32; the 8 largest
  s + b over ONE group (`num_expert_group` 1, `topk_group` 1:
  `use_grouped_topk` is then plain top-k), b a selection bias that takes no
  gradient; weights s[chosen] / (sum + 1e-20) (`moe_renormalize`) x 2.446;
  SwiGLU experts of 1024 and one shared SwiGLU of 1024 on every token; no
  auxiliary loss. NOT taken: softmax scores.

A SHARE: `experts_held` of `n_experts` (models/moe.py) and `vocab_size` rows
of the tables; both mixers' heads are held WHOLE (attention data-parallel
beside expert parallelism).

PRECISION, the one rule of the recurrent mixers (models/solar_open2.py):
parameters float32, compute bfloat16; the convolution with its SiLU and L2
norms, beta, the decay from its first factor's output on, every product of
the recurrence, the carried state, the gated norm, the router, both
softmaxes' statistics and the loss float32.

THE STACK: the leading dense layers one after another, then a `lax.scan`
over whole periods of kinds (a period's blocks unrolled in its body, each
rematerialised by itself), then the layers no whole period holds, one after
another (`plan`: the published 27 layers are the dense layer, six periods
KDA, KDA, MLA, KDA and the tail KDA, MLA; the benchmark's cell runs layers
1-5, the dense layer and ONE period).

THE TREE. `embed`, `lm_head`, `final_norm`; `dense_layers` (leaves stacked
over the leading dense layers: ln1, the mixer's leaves, ln2, w_gate, w_up
[D, dense_d_ff], w_down); `layers`: {"router_bias": [expert layers,
n_experts] (the selection biases of EVERY expert layer, in layer order: one
table), "period": {"0": .., "3": ..} (a period's blocks by position, leaves
stacked over the periods), "tail": {"0": ..} (unstacked; absent without
one)}. A KDA block's mixer leaves are models/solar_open2.py's; an MLA
block's: wq [D, H x 192], wkv_a [D, 512 + 64], kv_a_norm [512], wkv_b
[512, H x 256], wo [H x 128, D]; an expert block's feed-forward leaves
models/moe.py's.

Trained, not served: the engine refuses the model by name (a recurrent
state a head beside a latent cache is not built, and its experts are
training-only). Packed documents (`segment_ids`) are refused by name under
a KDA layer.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mla, moe, solar_open2
from ray_tpu.nn.layers import init_dense, rms_norm, swiglu
# by THIS name the benchmark's runner finds the rule the KDA sublayer runs, through the stack's
# own module (chipbench/runners/train_reference_solar_open2.py::program_rule): the name
# models/solar_open2.py binds, whose sublayer this stack calls
from ray_tpu.models.solar_open2 import kda_rule  # noqa: F401

Params = dict[str, Any]
KDA, MLA = "kda", "mla"
# what ops/kda.py's forward kernel writes (models/solar_open2.py has the bytes: at 32 heads
# 128 + 256 + 64 MiB a KDA layer)
REMAT_SAVES = solar_open2.REMAT_SAVES


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(moe.MoEConfig):
    """`mla_layers` is the PUBLISHED `full_attn_layers`, whole and numbered
    from 1; a configuration cut in depth (`n_layers` smaller) runs layers
    1 .. `n_layers`. `n_heads` are the MLA layers' heads, `kda_heads` the KDA
    layers'; `d_ff` ONE routed expert's width, `dense_d_ff` the leading dense
    layers'."""

    mla_layers: tuple = ()
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_rank: int = 128
    conv_kernel: int = 4
    kda_neg_eigval: bool = False   # beta = sigmoid, not doubled
    q_lora_rank: int = 0           # the query has no latent: ONE matrix
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64     # the channels of the ONE shared key; `mla_rope` false: unrotated
    v_head_dim: int = 128
    mla_rope: bool = False
    dense_d_ff: int = 9216
    first_dense_layers: int = 1
    # models/llama.py's seam: the module that builds this tree and runs these layers
    stack_module: str = "ray_tpu.models.kimi_linear"

    @property
    def head_dim(self) -> int:
        """Of an MLA layer's query and key."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.first_dense_layers

    @property
    def layer_types(self) -> tuple:
        """The kinds of the `n_layers` layers this configuration runs (by
        this name models/llama.py knows a stack of unlike layers)."""
        return tuple(MLA if l + 1 in self.mla_layers else KDA for l in range(self.n_layers))

    def _mixer_params(self, kind: str) -> tuple[int, int]:
        """(matmul parameters a token meets, the others) of one mixer."""
        d = self.d_model
        if kind == MLA:
            rkv = self.kv_lora_rank
            return (mla.query_params(self) + d * (rkv + self.qk_rope_head_dim)
                    + rkv * self.n_heads * (self.qk_nope_head_dim + self.v_head_dim)
                    + self.n_heads * self.v_head_dim * d, rkv)
        wide = self.kda_heads * self.kda_head_dim
        return (4 * d * wide + 2 * self.kda_rank * (d + wide) + d * self.kda_heads,
                3 * self.conv_kernel * wide + self.kda_heads + 2 * wide + self.kda_head_dim)

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires, every expert somewhere: 2 per
        matmul parameter it meets; an MLA layer's scores over the keys before
        it (192 channels of a key, 128 of a value); a KDA layer's recurrence
        in its position-by-position form (7 per element of a head's state)."""
        d = self.d_model
        total = 2.0 * d * self.vocab_size
        for l, kind in enumerate(self.layer_types):
            ffn = (3 * d * self.dense_d_ff if l < self.first_dense_layers else
                   d * self.n_experts + 3 * d * (self.top_k * self.d_ff + self.shared_d_ff))
            total += 2.0 * (self._mixer_params(kind)[0] + ffn)
            if kind == KDA:
                total += 7.0 * self.kda_heads * self.kda_head_dim ** 2
            else:
                total += 2.0 * (self.head_dim + self.v_head_dim) * self.n_heads * (seq_len + 1) / 2
        return total

    def num_params(self) -> int:
        d = self.d_model
        blocks = 0
        for l, kind in enumerate(self.layer_types):
            ffn = (3 * d * self.dense_d_ff if l < self.first_dense_layers else
                   d * self.n_experts + self.n_experts  # the router and its bias
                   + 3 * d * (self.n_held * self.d_ff + self.shared_d_ff))
            blocks += sum(self._mixer_params(kind)) + 2 * d + ffn
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + d + head + blocks


# moonshotai/Kimi-Linear-48B-A3B-Instruct config.json (the catalog's row): a dense layer,
# then (KDA, KDA, MLA, KDA) x 6, KDA, MLA: 20 KDA layers and 7 MLA layers
KIMI_LINEAR_48B_A3B = KimiLinearConfig(
    vocab_size=163840, d_model=2304, n_layers=27, n_heads=32, n_kv_heads=32, d_ff=1024,
    max_seq=1048576, rope_theta=10000.0, rms_eps=1e-5, tie_embeddings=False,
    n_experts=256, top_k=8, norm_topk_prob=True, router_aux_coeff=0.0, router_z_coeff=0.0,
    router_score="sigmoid", routed_scaling=2.446, shared_d_ff=1024,
    mla_layers=(4, 8, 12, 16, 20, 24, 27),
)
# the dense layer, two periods (KDA, MLA) and a tail of one MLA layer, small: 4 heads; keys of
# 12 + 4 beside values of 8; 12 experts of 32 (no product fills a tile)
KIMI_LINEAR_TINY = dataclasses.replace(
    KIMI_LINEAR_48B_A3B, vocab_size=512, d_model=64, n_layers=6, n_heads=4, n_kv_heads=4,
    d_ff=32, max_seq=512, remat=False, n_experts=12, top_k=4, shared_d_ff=32, kda_heads=4,
    kda_head_dim=16, kda_rank=16, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
    v_head_dim=8, dense_d_ff=96, mla_layers=(3, 5, 6),
)


# -- the stack's plan -------------------------------------------------------------


def plan(c: KimiLinearConfig) -> dict:
    """{"dense": the kinds of the leading dense layers, "period": the kinds
    of one period, "periods": how many whole ones, "tail": the kinds of the
    layers after the last whole period}. The period is the PUBLISHED
    stack's (`mla_layers` stays whole under a cut in depth): the one that
    leaves the fewest blocks to unroll there (a period's and the tail's),
    the shortest among equals. The published 27 layers end inside a period
    (layer 27 is an MLA layer where the period has a KDA layer), so their
    tail is no prefix of the period and `laguna.plan`'s rule (one period all
    through) finds none. A cut in depth runs the whole periods it reaches."""
    def kinds(n):
        return [MLA if l + 1 in c.mla_layers else KDA for l in range(n)][c.first_dense_layers:]

    published, rest = kinds(max(c.n_layers, max(c.mla_layers, default=0))), kinds(c.n_layers)
    dense = list(c.layer_types[:c.first_dense_layers])
    if not rest:
        raise ValueError("no expert layer after the dense ones")
    if len(set(dense)) > 1:   # they are ONE stacked subtree, `dense_layers`
        raise ValueError(f"the leading dense layers are of unlike kinds: {dense}")

    def whole(p, of):
        n = 1
        while of[n * p:(n + 1) * p] == of[:p]:
            n += 1
        return n

    p = min(range(1, len(published) + 1),
            key=lambda p: (p + len(published) - whole(p, published) * p, p))
    periods = min(whole(p, published), len(rest) // p)
    return {"dense": dense, "period": published[:p],
            "periods": periods, "tail": rest[periods * p:]}


# -- the tree ---------------------------------------------------------------------


def _block_axes(c: KimiLinearConfig, kind: str, dense: bool, stacked: bool = True) -> Params:
    mixer = mla.attention_axes(c) if kind == MLA else solar_open2.attention_axes(c, KDA)
    if dense:
        ffn = dict(llama.DENSE_FFN_AXES)
    else:
        ffn = moe.expert_axes(c)
        del ffn["router_bias"]   # the table beside the periods
    axes = {"ln1": ("layers", "norm"), **mixer, "ln2": ("layers", "norm"), **ffn}
    return axes if stacked else {k: v[1:] for k, v in axes.items()}


def logical_axes(c: KimiLinearConfig) -> Params:
    """Of the whole tree `init_params` makes."""
    p = plan(c)
    layers: Params = {"router_bias": ("layers", "expert"),
                      "period": {str(j): _block_axes(c, kind, False)
                                 for j, kind in enumerate(p["period"])}}
    if p["tail"]:
        layers["tail"] = {str(j): _block_axes(c, kind, False, stacked=False)
                          for j, kind in enumerate(p["tail"])}
    axes: Params = {"embed": ("vocab", "embed"), "layers": layers, "final_norm": ("norm",)}
    if p["dense"]:
        axes["dense_layers"] = _block_axes(c, p["dense"][0], True)
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _block_params(c: KimiLinearConfig, key: jax.Array, kind: str, n: int, dense: bool) -> Params:
    """`n` blocks of one kind, leaves stacked over them."""
    d, pd = c.d_model, c.param_dtype
    k_mix, k_ffn = jax.random.split(key)
    of_n = dataclasses.replace(c, n_layers=n)
    mixer = (mla.attention_params(of_n, (k_mix,)) if kind == MLA
             else solar_open2.attention_params(c, k_mix, KDA, n))
    if dense:
        keys = jax.random.split(k_ffn, 3)
        ffn = {"w_gate": llama.stacked_dense(keys[0], n, (d, c.dense_d_ff), pd),
               "w_up": llama.stacked_dense(keys[1], n, (d, c.dense_d_ff), pd),
               "w_down": llama.stacked_dense(keys[2], n, (c.dense_d_ff, d), pd)}
    else:
        ffn = moe.expert_params(of_n, k_ffn)
        del ffn["router_bias"]
    return {"ln1": jnp.ones((n, d), pd), **mixer, "ln2": jnp.ones((n, d), pd), **ffn}


def init_params(c: KimiLinearConfig, key: jax.Array) -> Params:
    """The whole tree (the module's docstring)."""
    p = plan(c)
    k_embed, k_head, k_dense, k_period, k_tail = jax.random.split(key, 5)
    layers: Params = {
        "router_bias": jnp.zeros((c.n_expert_layers, c.n_experts), c.param_dtype),
        "period": {str(j): _block_params(c, jax.random.fold_in(k_period, j), kind, p["periods"],
                                         dense=False)
                   for j, kind in enumerate(p["period"])}}
    if p["tail"]:
        layers["tail"] = {
            str(j): jax.tree.map(lambda w: w[0], _block_params(
                c, jax.random.fold_in(k_tail, j), kind, 1, dense=False))
            for j, kind in enumerate(p["tail"])}
    params: Params = {
        "embed": init_dense(k_embed, (c.vocab_size, c.d_model), c.param_dtype, scale=1.0),
        "layers": layers,
        "final_norm": jnp.ones((c.d_model,), c.param_dtype),
    }
    if p["dense"]:
        params["dense_layers"] = _block_params(c, k_dense, p["dense"][0], len(p["dense"]),
                                               dense=True)
    if not c.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (c.d_model, c.vocab_size), c.param_dtype)
    return params


# -- the block and the stack --------------------------------------------------------


def _block(h: jax.Array, lp: Params, *, c: KimiLinearConfig, kind: str, dense: bool,
           positions: jax.Array, segment_ids: Optional[jax.Array]
           ) -> tuple[jax.Array, Optional[Params]]:
    """One decoder layer of one kind -> (h, the expert layer's statistics;
    None for a dense layer). An expert block's `lp` carries its row of the
    selection-bias table as `router_bias`. The mixers' scopes are their
    modules' (`kda.*`, `mla.*`); the dense SwiGLU stands under `dense.ffn`."""
    with jax.named_scope("block.norm"):
        u = rms_norm(h, lp["ln1"], c.rms_eps)
    if kind == KDA:
        y = solar_open2.kda_sublayer(u, lp, c, segment_ids=segment_ids)
    else:
        y = mla.mla_sublayer(u, lp, c, positions=positions, segment_ids=segment_ids)
    # the residual add stands in the scope of the sublayer's last matmul, which it fuses into
    with jax.named_scope(f"{kind}.out"):
        h = h + y
    with jax.named_scope("block.norm"):
        u = rms_norm(h, lp["ln2"], c.rms_eps)
    if dense:
        with jax.named_scope("dense.ffn"):
            return h + swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    y, stats, _ = moe.moe_ffn(u, lp, c)
    with jax.named_scope("moe.combine"):
        return h + y, stats


def trunk(params: Params, tokens: jax.Array, c: KimiLinearConfig, *, positions: jax.Array,
          segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Params]:
    """The layers, up to the last one's output before the final norm ->
    (h [B, S, D], the expert layers' statistics, leaves stacked over them in
    layer order). `positions` reach the MLA sublayer, which reads them only
    under a rotary (`mla_rope`; none as published)."""
    p = plan(c)
    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens]

    def block_of(kind, dense=False):
        return llama._remat(partial(_block, c=c, kind=kind, dense=dense, positions=positions,
                                    segment_ids=segment_ids), c)

    layers = params["layers"]
    bias = layers["router_bias"]
    n, per = p["periods"], len(p["period"])
    # as models/llama.py's: under this name stand the scan's own slices and stacked
    # writes; every block's operations stand under a scope of their own inside it
    with jax.named_scope("block.stack"):
        if p["dense"]:
            h = llama.run_dense_layers(h, params, len(p["dense"]),
                                       block_of(p["dense"][0], dense=True))
        blocks = [block_of(kind) for kind in p["period"]]

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
        for j, kind in enumerate(p["tail"]):
            h, s = block_of(kind)(h, {**layers["tail"][str(j)], "router_bias": bias[n * per + j]})
            stats = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]), stats, s)
    return h, stats
