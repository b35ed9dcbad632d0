"""Nemotron-H: Mamba-2 state-space mixers, expert layers and attention
layers in one stack, ONE sublayer a layer.

What the causal tower of Nemotron-Labs-TwoTower-30B-A3B
(nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, `model_type`
nemotron_h) adds to the one decoder of models/llama.py:
`NemotronHConfig`; the state-space sublayer `mamba_sublayer` (the scan
itself is ops/ssd.py's two Pallas kernels, which read the convolution's
output where ops/gdn_conv.py's kernels, its bias and SiLU among them,
leave it, and the gated norm after it ops/gated_norm.py's two, which read
the scan's where it leaves it); an attention sublayer without a rotary at
32 query heads over 2 key-value heads; the expert layer of
models/moe.py with experts of TWO matrices (`expert_act` "relu2"); and
a parameter tree and a stack built from the PATTERN STRING
(`hybrid_override_pattern`: `M` a Mamba-2 mixer, `E` an expert layer,
`*` an attention layer). The head, the loss and the train step are
models/llama.py's, which hands `logical_axes`, `init_params` and the
trunk to the module the configuration names (`stack_module`).

u is a sublayer's input, RMSNorm(h) at `rms_eps`; EVERY layer is
h = h + f(RMSNorm(h)) with ONE f, a final RMSNorm before the untied head.

`M`, Mamba-2 (`mamba_heads` 64 of `mamba_head_dim` P = 64: the inner
width is 64 x 64 = 4,096 and NOT `expand` x hidden = 5,376, which the
published config carries unused; `ssm_groups` G = 8, `ssm_state` N =
128, `conv_kernel` 4 with a bias, no bias on the projections):

  [z | xBC | dt] = u W_in, widths 4,096 | 4,096 + 2 x 8 x 128 = 6,144 | 64
  (ONE leaf `w_in` [D, 10,304]);
  xBC = SiLU(conv(xBC) + b): causal, depthwise, tap j on position t - j,
  zeros before the sequence (ops/gdn_conv.py, the 6,144 channels as 48
  heads of 128); x [64 heads, 64], B, C [8 groups, 128]; head h reads
  group h // 8 (the OTHER reading, h % 8, is refused by the
  benchmark's one-thing-wrong table);
  dt = softplus(dt + dt_bias) a head (`time_step_limit` (0, inf): no
  clamp); A = -exp(A_log) a head;
  H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T from H = 0 (H [64, 128] a
  head), y_t = H_t C_t + D x_t (ops/ssd.py, chunks of `chunk_size`: the
  convolution's [B, 48, S, 128] float32 is the kernels' `xbc` as it
  stands, x's 32 heads of 128 two heads of 64 side by side, then B's 8
  groups, then C's; y comes back token-major [B, S, 4096], as the gated
  norm reads it, and the backward writes dx, dB and dC into one array
  the convolution's backward reads);
  y = GroupRMSNorm(y * SiLU(z)) over groups of 4,096 / 8 = 512 with one
  learned [4,096] weight: the gate BEFORE the norm (the other reading,
  the norm first, is refused likewise; ops/gated_norm.py, one pass forward
  and one backward on y and z as they stand, whose dy the scan's backward
  kernel reads as it is written); out = y W_out.

`E`: logits u W_r in float32 [128]; s = sigmoid(logits); the `top_k`
largest of s + `router_bias` (`n_group` 1: no groups); weights s of the
chosen over their sum (+ 1e-20), x `routed_scaling`; an expert is
W_down relu(W_up x)^2 (no gate, no bias); plus the shared expert, the
same form at `shared_d_ff`, on every token (models/moe.py).

`*`: q [32 x 128], k, v [2 x 128] = u Wq, u Wk, u Wv, causal softmax
attention at 1 / sqrt(128), Wo, no bias. NO rotary: the family applies
no positional embedding in its attention layers, position reaches them
through the Mamba layers' state (`rope_theta` and
`partial_rotary_factor` stand in the published config unused; the other
reading, a rotary at theta 10000 on the whole head, is a row of the
benchmark's table).

PRECISION, the rule models/olmo_hybrid.py keeps too: parameters float32,
compute bfloat16; the convolution with its SiLU, dt, the decay and its
cumulative sums, every product of the scan, the carried state, the gated
norm, the router's logits, softmax and the loss float32.

INITIALISATION as the family's: `A_log` = log of uniform (1, 16); dt
log-uniform in (`time_step_min`, `time_step_max`) floored at
`time_step_floor`, stored through the inverse of softplus; D = 1; the
convolution's bias uniform in +-1 / sqrt(taps) (a Conv1d's default);
`rescale_prenorm_residual`: a Mamba mixer's output projection divided by
sqrt(`published_layers`), the PUBLISHED depth whatever depth is run.

THE STACK (`segments`): the first `n_layers` characters of the pattern,
cut greedily into runs `(unit, n)`: a unit that repeats n >= 2 times is
a `lax.scan` over its repetitions, its blocks unrolled in the body; what
repeats nowhere is an unrolled block. Every block is rematerialised by
itself. The published 52 are (`MEMEM*E` x 5), (`ME` x 3), `M`, `*`,
(`EM` x 4), `E`; the benchmark's nine, `MEMEM*EME`, are (`ME` x 2), `M`,
`*`, `E`, `M`, `E`: any cut is the same function.

THE TREE. `embed`, `lm_head`, `final_norm`; `layers`: the layers of a
KIND stacked in their order: "mamba" (`ln`, `w_in` [D, 10304], `conv`
[K, 6144], `conv_bias` [6144], `dt_bias`, `A_log`, `D` [heads], `norm`
[4096], `w_out` [4096, D]), "attention" (`ln`, `wq`, `wk`, `wv`, `wo`),
"experts" (`ln` and models/moe.py's leaves without a gate: `router`,
`shared_up`, `shared_down`, `w_up`, `w_down`), and `router_bias`
[expert layers, n_experts], the selection biases as ONE table.

NOT IMPLEMENTED, refused by name: the published model's SECOND tower (a
denoiser of the same pattern: a modulation of its norms, attention that
is bidirectional inside a block, conditioning on this tower) and its
diffusion objective, of which the published config gives no size and no
equation: this module is the causal tower, trained under next-token
cross-entropy; `-` (dense MLP) layers of the family's other models;
serving (the engine refuses the model by name: a state-space state
beside the pages).

PACKED DOCUMENTS (`segment_ids` [B, S]; PR 66, for models/granite_hybrid.py,
which runs this module's `mamba_sublayer` and `attention_sublayer` as they
stand): a Mamba layer hands the ids to its convolution (ops/gdn_conv.py: tap
j of position t reads t - j only inside t's document) and to its scan
(ops/ssd.py: the state a position reads holds nothing of an earlier
document, H_{t-1} taken as 0 where the document changes), an attention
layer to the flash kernels' mask; an expert layer reads no other position.
Without ids every sublayer is traced as it was.
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
from ray_tpu.models import llama, moe
from ray_tpu.nn.layers import head_major, init_dense, rms_norm
from ray_tpu.ops.attention import attention_head_major
from ray_tpu.ops.gated_norm import gated_norm
from ray_tpu.ops.gdn_conv import gdn_conv
# by the name `ssd_scan` the benchmark's runner finds the scan and holds it alone to the
# position-by-position reference; the sublayer runs the SAME two kernels through
# `ssd_scan_lanes`, which takes the convolution's array as it stands where `ssd_scan` builds
# it from the plain [B, heads, S, P] arrays (tests/test_ssd.py holds the two to the bit)
from ray_tpu.ops.ssd import ssd_scan, ssd_scan_lanes  # noqa: F401 - `ssd_scan` is read by name

Params = dict[str, Any]
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# a kind's group of stacked leaves under params["layers"]
GROUP = {MAMBA: "mamba", EXPERTS: "experts", ATTENTION: "attention"}
_F32 = jnp.float32
# saved by llama._remat's "dots" policy beside its own names: ops/ssd.py's forward kernel's y
# and the chunks' starting states, which is all its backward kernel reads beside the inputs
REMAT_SAVES = ("ssd_out", "ssd_states")
_CONV_HEAD = 128   # the convolution's channels go through ops/gdn_conv.py as heads of 128


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(moe.MoEConfig):
    """`pattern` is the PUBLISHED string, whole; a configuration cut in
    depth (`n_layers` smaller) runs its first `n_layers` characters.
    `n_heads` / `n_kv_heads` / `head_dim` are the attention layers',
    `d_ff` ONE routed expert's width."""

    pattern: str = ""
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # `rescale_prenorm_residual` divides by the root of the PUBLISHED depth
    published_layers: int = 52
    # models/llama.py's seam: the module that builds this tree and runs these layers
    stack_module: str = "ray_tpu.models.nemotron_h"

    @property
    def layer_types(self) -> tuple:
        """The kinds of the `n_layers` layers this configuration runs
        (by this name models/llama.py knows a stack of unlike layers)."""
        if len(self.pattern) < self.n_layers:
            raise ValueError(f"{self.n_layers} layers, but the pattern names {len(self.pattern)}")
        kinds = tuple(self.pattern[:self.n_layers])
        unknown = sorted(set(kinds) - set(GROUP))
        if unknown:
            raise NotImplementedError(
                f"layer kinds {unknown} of the pattern {self.pattern!r}: M (Mamba-2), E (experts) "
                "and * (attention) are implemented; a dense MLP layer (-) is not")
        return kinds

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    def _matmul_params(self, kind: str, experts: int) -> int:
        """Matmul parameters of one layer of `kind`, `experts` routed experts counted."""
        d = self.d_model
        if kind == MAMBA:
            return d * (self.mamba_inner + self.conv_channels + self.mamba_heads) \
                + self.mamba_inner * d
        if kind == ATTENTION:
            return 2 * d * self.head_dim * (self.n_heads + self.n_kv_heads)
        return d * self.n_experts + self.expert_matrices * d * (experts * self.d_ff
                                                                 + self.shared_d_ff)

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires in the WHOLE model, every
        expert somewhere: 2 per matmul parameter it meets; an attention
        layer's scores over the keys before it; a Mamba layer's scan in
        its position-by-position form, 5 per element of a head's state
        (the decay, the write x B^T and its add, the read-out H C). The
        convolution's taps, the norms and the gates are elementwise and
        do not count."""
        total = 2.0 * self.d_model * self.vocab_size
        for kind in self.layer_types:
            total += 2.0 * self._matmul_params(kind, self.top_k)
            if kind == MAMBA:
                total += 5.0 * self.mamba_inner * self.ssm_state
            elif kind == ATTENTION:
                total += 4.0 * self.head_dim * self.n_heads * (seq_len + 1) / 2
        return total

    def num_params(self) -> int:
        d = self.d_model
        own = {MAMBA: (self.conv_kernel + 1) * self.conv_channels + 3 * self.mamba_heads
               + self.mamba_inner, ATTENTION: 0, EXPERTS: self.n_experts}   # the selection bias
        blocks = sum(self._matmul_params(kind, self.n_held) + own[kind] + d
                     for kind in self.layer_types)
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + d + head + blocks


# nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 config.json (the catalog's row): the CAUSAL
# tower, 23 Mamba-2, 23 expert and 6 attention layers
NEMOTRON_TWOTOWER_30B_A3B = NemotronHConfig(
    vocab_size=131072, d_model=2688, n_layers=52, n_heads=32, n_kv_heads=2, d_ff=1856,
    max_seq=262144, rope_theta=0.0, rms_eps=1e-5, tie_embeddings=False,
    n_experts=128, top_k=6, norm_topk_prob=True, router_aux_coeff=0.0, router_z_coeff=0.0,
    router_score="sigmoid", routed_scaling=2.5, shared_d_ff=3712, expert_act="relu2",
    pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
)
# all three kinds, a unit that repeats (`ME*` x 2) and a tail; 8 heads of 16 in 2 groups, a
# state of 64, chunks of 16: no product fills a tile (the convolution takes x and each of B
# and C as whole heads of 128: 8 x 16 + 2 x 2 x 64 = 384 channels, three of them)
NEMOTRON_H_TINY = dataclasses.replace(
    NEMOTRON_TWOTOWER_30B_A3B, vocab_size=512, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
    d_ff=32, max_seq=512, remat=False, n_experts=16, top_k=3, shared_d_ff=48, head_dim=16,
    mamba_heads=8, mamba_head_dim=16, ssm_groups=2, ssm_state=64, chunk_size=16,
    pattern="ME*ME*MEMEM*", published_layers=12,
)


# -- the stack's plan ----------------------------------------------------------


def segments(kinds: tuple) -> list:
    """[(unit, n)]: the kinds in order, cut greedily into runs; at each
    place the unit whose n >= 2 repetitions cover the most layers, else
    one layer by itself (n = 1)."""
    out, i = [], 0
    while i < len(kinds):
        best = (1, 1)
        for p in range(1, (len(kinds) - i) // 2 + 1):
            n = 1
            while kinds[i + n * p:i + (n + 1) * p] == kinds[i:i + p]:
                n += 1
            if n >= 2 and n * p > best[0] * best[1]:
                best = (p, n)
        p, n = best
        out.append(("".join(kinds[i:i + p]), n))
        i += p * n
    return out


# -- the tree ---------------------------------------------------------------------


def _stacked(n: int, key: jax.Array, shape: tuple, dtype, scale: Optional[float] = None):
    return jax.vmap(lambda k: init_dense(k, shape, dtype, scale))(jax.random.split(key, n))


def _group_axes(c: NemotronHConfig, kind: str) -> Params:
    ln = {"ln": ("layers", "norm")}
    if kind == ATTENTION:
        return {**ln, "wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "kv_heads"),
                "wv": ("layers", "embed", "kv_heads"), "wo": ("layers", "heads", "embed")}
    if kind == EXPERTS:
        axes = moe.expert_axes(c)
        del axes["router_bias"]   # the table beside the groups
        return {**ln, **axes}
    return {**ln, "w_in": ("layers", "embed", "heads"), "conv": ("layers", None, "heads"),
            "conv_bias": ("layers", "heads"), "dt_bias": ("layers", None),
            "A_log": ("layers", None), "D": ("layers", None), "norm": ("layers", "norm"),
            "w_out": ("layers", "heads", "embed")}


def logical_axes(c: NemotronHConfig) -> Params:
    """Of the whole tree `init_params` makes."""
    layers: Params = {GROUP[k]: _group_axes(c, k) for k in GROUP if c.count(k)}
    if c.count(EXPERTS):
        layers["router_bias"] = ("layers", "expert")
    axes: Params = {"embed": ("vocab", "embed"), "layers": layers, "final_norm": ("norm",)}
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def mamba_params(c: NemotronHConfig, key: jax.Array, n: int = 1) -> Params:
    """`n` Mamba-2 mixers, leaves stacked over them (the module's
    docstring has the initialisation)."""
    d, H, K, pd = c.d_model, c.mamba_heads, c.conv_kernel, c.param_dtype
    keys = jax.random.split(key, 6)
    lo, hi = math.log(c.time_step_min), math.log(c.time_step_max)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(keys[0], (n, H), _F32) * (hi - lo) + lo),
                     c.time_step_floor)
    bound = 1.0 / math.sqrt(K)
    return {
        "w_in": _stacked(n, keys[1], (d, c.mamba_inner + c.conv_channels + H), pd),
        # a tap's fan-in is the K positions it sums
        "conv": _stacked(n, keys[2], (K, c.conv_channels), pd, bound),
        "conv_bias": jax.random.uniform(keys[3], (n, c.conv_channels), _F32,
                                        -bound, bound).astype(pd),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "A_log": jnp.log(jax.random.uniform(keys[4], (n, H), _F32, 1.0, 16.0)).astype(pd),
        "D": jnp.ones((n, H), pd),
        "norm": jnp.ones((n, c.mamba_inner), pd),
        "w_out": _stacked(n, keys[5], (c.mamba_inner, d), pd,
                          1.0 / math.sqrt(c.mamba_inner * c.published_layers)),
    }


def attention_params(c: NemotronHConfig, key: jax.Array, n: int = 1) -> Params:
    d, hd, pd = c.d_model, c.head_dim, c.param_dtype
    keys = jax.random.split(key, 4)
    return {"wq": _stacked(n, keys[0], (d, c.n_heads * hd), pd),
            "wk": _stacked(n, keys[1], (d, c.n_kv_heads * hd), pd),
            "wv": _stacked(n, keys[2], (d, c.n_kv_heads * hd), pd),
            "wo": _stacked(n, keys[3], (c.n_heads * hd, d), pd)}


def init_params(c: NemotronHConfig, key: jax.Array) -> Params:
    """The whole tree (the module's docstring)."""
    d = c.d_model
    k_embed, k_head, k_mamba, k_attn, k_experts = jax.random.split(key, 5)
    make = {MAMBA: lambda n: mamba_params(c, k_mamba, n),
            ATTENTION: lambda n: attention_params(c, k_attn, n),
            EXPERTS: lambda n: moe.expert_params(dataclasses.replace(c, n_layers=n), k_experts)}
    layers: Params = {}
    for kind, name in GROUP.items():
        n = c.count(kind)
        if n:
            layers[name] = {"ln": jnp.ones((n, d), c.param_dtype), **make[kind](n)}
    if c.count(EXPERTS):
        layers["router_bias"] = layers["experts"].pop("router_bias")
    params: Params = {
        "embed": init_dense(k_embed, (c.vocab_size, d), c.param_dtype, scale=1.0),
        "layers": layers,
        "final_norm": jnp.ones((d,), c.param_dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (d, c.vocab_size), c.param_dtype)
    return params


# -- the sublayers ------------------------------------------------------------------


def mamba_sublayer(u: jax.Array, lp: Params, c: NemotronHConfig, *,
                   segment_ids: Optional[jax.Array]) -> jax.Array:
    """The sublayer's input u [B, S, D] -> the Mamba-2 mixer's output
    [B, S, D] (the module's docstring has the equations). Named scopes on
    the device ops, forward and backward: `ssm.proj`, `ssm.conv` (one
    `gdn_conv_fwd` / `gdn_conv_bwd` kernel over the 48 heads of 128 and
    the sum of the taps' and the bias's gradients), `ssm.gates`,
    `ssm.scan` (one `ssd_scan_fwd` / `ssd_scan_bwd` kernel and the [B,
    heads, S] arithmetic of dt A and its gradients), `ssm.norm` (one
    `gated_norm_fwd` / `gated_norm_bwd` kernel and the sum of the weight's
    gradient over 8 sublanes), `ssm.out`. With `segment_ids` [B, S] (packed
    documents) the convolution and the scan stop at a document's boundary;
    the projections, the gates and the norm read one position each."""
    D = u.shape[2]
    P, G, N, dt_ = c.mamba_head_dim, c.ssm_groups, c.ssm_state, u.dtype
    inner, wide = c.mamba_inner, c.conv_channels
    if inner % _CONV_HEAD or _CONV_HEAD % P or G * N % _CONV_HEAD or (
            N % _CONV_HEAD and _CONV_HEAD % N):
        raise NotImplementedError(
            f"{inner} channels of x in heads of {P}, {G} groups of a state of {N}: the "
            f"convolution takes x, B and C as whole heads of {_CONV_HEAD} channels")
    with obs.layer_span("ssm.mixer"):  # counts engaged sites, while tracing
        with jax.named_scope("ssm.proj"):
            w_in = lp["w_in"].astype(dt_)
            z = jnp.einsum("bsd,dk->bsk", u, w_in[:, :inner])
            xBC = head_major(jnp.einsum(
                "bsd,dnh->bnsh", u, w_in[:, inner:inner + wide].reshape(D, -1, _CONV_HEAD)))
            # float32 out of the matmul: the step's logits are not rounded to the compute type
            dt = jnp.einsum("bsd,dh->bhs", u.astype(_F32), w_in[:, inner + wide:].astype(_F32))
        with jax.named_scope("ssm.conv"):
            xBC = gdn_conv(xBC, lp["conv"], bias=lp["conv_bias"],
                           segment_ids=segment_ids)                 # [B, wide / 128, S, 128]
        with jax.named_scope("ssm.gates"):
            dt = jax.nn.softplus(dt + lp["dt_bias"].astype(_F32)[:, None])
            A = -jnp.exp(lp["A_log"].astype(_F32))
        with jax.named_scope("ssm.scan"):
            # the convolution's heads of 128 as lane blocks of N (ops/ssd.py's layout: x's
            # blocks, then B's, then C's): at a state of 128 the array as it stands
            y = ssd_scan_lanes(_lane_blocks(xBC, N), dt, A, lp["D"], head_dim=P,
                               chunk=c.chunk_size, segment_ids=segment_ids)   # [B, S, inner] f32
        with jax.named_scope("ssm.norm"):
            y = gated_norm(y, z, lp["norm"], groups=G, eps=c.rms_eps)   # [B, S, inner] as z
        with jax.named_scope("ssm.out"):
            return jnp.einsum("bsk,kd->bsd", y, lp["w_out"].astype(dt_))


def _lane_blocks(v: jax.Array, width: int) -> jax.Array:
    """[B, heads, S, d] -> [B, heads x d / width, S, width], the channels in their order."""
    B, heads, S, d = v.shape
    if width == d:
        return v
    if width > d:   # a block spans whole heads
        return v.reshape(B, -1, width // d, S, d).swapaxes(2, 3).reshape(B, -1, S, width)
    return v.reshape(B, heads, S, d // width, width).swapaxes(2, 3).reshape(B, -1, S, width)


def attention_sublayer(u: jax.Array, lp: Params, c: NemotronHConfig, *,
                       segment_ids: Optional[jax.Array]) -> jax.Array:
    """u [B, S, D] -> the attention mixer's output: GQA without a rotary,
    head-major from the projections to `wo` (models/gqa.py's layout).
    Scopes `attn.qkv`, `attn.attend`, `attn.out`."""
    B, S, D = u.shape
    hd, dt = c.head_dim, u.dtype
    with jax.named_scope("attn.qkv"):
        q, k, v = (head_major(jnp.einsum("bsd,dnh->bnsh", u, lp[n].astype(dt).reshape(D, -1, hd)))
                   for n in ("wq", "wk", "wv"))
    with jax.named_scope("attn.attend"):
        o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                 impl=c.attention_impl)
        # saved by the "dots" remat policy, as models/gqa.py's is
        o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope("attn.out"):
        return jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(dt).reshape(c.n_heads, hd, D))


def _block(h: jax.Array, lp: Params, *, c: NemotronHConfig, kind: str,
           segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Optional[Params]]:
    """One layer, ONE sublayer: h + f(RMSNorm(h)) -> (h, the expert
    layer's statistics; None for a mixer)."""
    with jax.named_scope("block.norm"):
        u = rms_norm(h, lp["ln"], c.rms_eps)
    if kind == EXPERTS:
        y, stats, _ = moe.moe_ffn(u, lp, c)
        with jax.named_scope("moe.combine"):
            return h + y, stats
    mixer = mamba_sublayer if kind == MAMBA else attention_sublayer
    y = mixer(u, lp, c, segment_ids=segment_ids)
    # the residual add stands in the scope of the sublayer's last matmul, which it fuses into
    with jax.named_scope("ssm.out" if kind == MAMBA else "attn.out"):
        return h + y, None


def trunk(params: Params, tokens: jax.Array, c: NemotronHConfig, *, positions: jax.Array,
          segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Optional[Params]]:
    """The layers, up to the last one's output before the final norm ->
    (h [B, S, D], the expert layers' statistics, leaves stacked over them
    in layer order; None without an expert layer). `positions` are not
    read: no layer has a rotary, the state carries the order."""
    layers = params["layers"]
    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens]
    blocks = {kind: llama._remat(partial(_block, c=c, kind=kind, segment_ids=segment_ids), c)
              for kind in GROUP}
    done = dict.fromkeys(GROUP, 0)   # layers of each kind already run
    all_stats = []

    def of_kind(kind):
        """A kind's stacked leaves, the selection biases among an expert layer's."""
        if kind == EXPERTS:
            return {**layers[GROUP[kind]], "router_bias": layers["router_bias"]}
        return layers[GROUP[kind]]

    # as models/llama.py's: under this name stand the scans' own slices and stacked
    # writes; every block's operations stand under a scope of their own inside it
    with jax.named_scope("block.stack"):
        for unit, n in segments(c.layer_types):
            # (in GROUP's order, not a set's: the order of tracing is the lowered text's)
            per = {kind: unit.count(kind) for kind in GROUP if kind in unit}
            if n == 1:   # what repeats nowhere: ONE layer, its leaves sliced where they stand
                (kind,) = unit
                h, stats = blocks[kind](h, jax.tree.map(lambda w: w[done[kind]], of_kind(kind)))
                stats = None if stats is None else jax.tree.map(lambda a: a[None], stats)
            else:
                lps = {kind: jax.tree.map(
                    lambda w: w[done[kind]:done[kind] + n * per[kind]].reshape(
                        (n, per[kind]) + w.shape[1:]), of_kind(kind)) for kind in per}

                def run_unit(h, lps, unit=unit):
                    at, stats = dict.fromkeys(lps, 0), []
                    for kind in unit:
                        h, s = blocks[kind](h, jax.tree.map(lambda w: w[at[kind]], lps[kind]))
                        at[kind] += 1
                        if s is not None:
                            stats.append(s)
                    return h, (jax.tree.map(lambda *a: jnp.stack(a), *stats) if stats else None)

                h, stats = jax.lax.scan(run_unit, h, lps)
                if stats is not None:
                    stats = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), stats)
            if stats is not None:
                all_stats.append(stats)
            for kind in per:
                done[kind] += n * per[kind]
    if not all_stats:
        return h, None
    return h, jax.tree.map(lambda *a: jnp.concatenate(a), *all_stats)
