"""Attention over the keys a learned indexer selects (DeepSeek sparse
attention, DSA) and the Keye-VL-2.0 language model's configuration.

What Keye-VL-2.0-30B-A3B's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B,
`model_type` KeyeVL2; the indexer as DeepSeek-V3.2's public inference
code computes it) adds to the one decoder of models/llama.py:
`KeyeConfig`, and the attention sublayer `dsa_sublayer` with its
parameters and their logical axes. The block, the layer scan, the head
and the loss are models/llama.py's, which runs `dsa_sublayer` as the row
"dsa" of its `MIXERS`, the kind a `KeyeConfig` names; the
expert layer (softmax top-8 of 128 chosen with a selection bias,
renormalised, a share of the experts held) is models/moe.py's.

THE SUBLAYER. With x = RMSNorm(hidden), t a query and s <= t a key (rows
of the sequence, 0 .. T - 1), H query heads over G key-value heads of
`head_dim` (explicit: not d_model / heads), J indexer heads of
`indexer_head_dim`, no bias:

  main        q_t,h = rope(norm_q((x_t Wq)_h)), k_s,g = rope(norm_k((x_s Wk)_g)),
              v_s,g = (x_s Wv)_g; norm_q and norm_k an RMSNorm over the
              channels of EACH head with one learned [head_dim] weight
              each (MoEConfig's `qk_norm` is OLMoE's, over the whole
              projected width: not this one); rotary at `rope_theta` on
              the whole head, half-split pairing;
  indexer     qI_t,j = rope((x_t W_Iq)_j), kI_s = rope(LayerNorm(x_s W_Ik)):
              ONE key for the J heads; w_t = x_t W_Iw in R^J;
              I_t,s = sum_j w_t,j ReLU(qI_t,j . kI_s) / sqrt(J x indexer_head_dim),
              accumulated and compared in float32;
  selection   S_t = every s <= t where t < `indexer_topk`, else the
              `indexer_topk` keys s <= t of largest I_t,s, ties to the
              lower s: EXACT (`select_keys`);
  attend      o_t,h = sum over S_t of softmax_{s in S_t}(q_t,h . k_s,g / sqrt(head_dim)) v_s,g;
  out         hidden += concat_h(o_t,h) Wo.

The selection is discrete: it takes no gradient and hands none on. The
indexer reads x and its own weights behind `stop_gradient`, so W_Iq,
W_Ik, W_Iw and the LayerNorm are parameters the language-model loss
never moves (a deployment trains them by an auxiliary loss that aligns
softmax(I) with the main attention's probabilities: NOT implemented,
the flash kernels do not give those out).

HOW IT RUNS. q, k, v and o are head-major from the projections to `wo`
(models/gqa.py's layout paragraph). The index scores are walked in
chunks of `index_chunk` queries (the config's `q_chunk_size`; it changes
no result): a chunk's scores are [B, J, chunk, keys up to the chunk's
last row] float32 for one fused pass and [B, chunk, keys] after it, so
no [T, T] array a head and no [B, heads, T, T] array ever exists, and
the chunks wholly before row `indexer_topk` compute nothing (every
visible key is selected). The product is spelled with a batch dimension
and everything of the indexer sits behind `stop_gradient`: the "dots"
remat policy saves none of it, and the backward reads none of it.
The k-th largest score of a row is found by bisection on the bits of
the float32 scores read as ordered integers (32 counting passes over
the chunk: exact, no sort), the cut among equal scores by a running
count taken only where a row's cut fell on equal scores. The selection
crosses to the attention kernels PACKED, one bit a (query, key) pair
(ops/flash.py::pack_selection: 8 MiB a layer at 8192 tokens), is saved
for the backward under the name `dsa_sel` (its row of `llama.MIXERS`) and
is a constant there. The kernels visit every sub-tile under the
diagonal and mask inside it.

Trained, not served: the engine refuses every expert configuration, and
a cache of indexer keys and a selection in ops/ragged.py do not exist.
Refused by name in models/registry.py: image or video inputs (the
27-layer vision tower, position streams that differ under
`mrope_section`: for text the three streams are equal and the sectioned
rotary IS the plain one), a sliding window, attention bias.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import moe
from ray_tpu.nn.layers import head_major, init_dense, rms_norm, rope_tables, rotate_head_major
from ray_tpu.ops.attention import attention_head_major
from ray_tpu.ops.flash import pack_selection

Params = dict[str, Any]
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class KeyeConfig(moe.MoEConfig):
    """The attention's own sizes; the expert layer's are `MoEConfig`'s.
    `d_ff` is the width of one expert."""

    mixer: ClassVar[str] = "dsa"
    head_dim: int = 128            # explicit: 2048 / 32 is 64
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048       # keys a query attends to
    index_chunk: int = 512         # queries a chunk of the index scores (`q_chunk_size`)
    indexer_norm_eps: float = 1e-6

    def _attention_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        main = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads) + 2 * hd
        J, ihd = self.indexer_heads, self.indexer_head_dim
        return main + d * (J * ihd + ihd + J) + 2 * ihd

    def selected_keys(self, seq_len: int) -> float:
        """Keys a query attends to, in the mean over a sequence."""
        k = min(self.indexer_topk, seq_len)
        return (k * (k + 1) / 2 + (seq_len - k) * k) / seq_len

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires in the WHOLE model, every expert
        somewhere: 2 per matmul parameter it meets (projections, the
        indexer's three, the router, its `top_k` experts, the head), the
        index scores over every key before it and the attention over the
        SELECTED keys alone."""
        d, hd, J, ihd = self.d_model, self.head_dim, self.indexer_heads, self.indexer_head_dim
        proj = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads) + d * (J * ihd + ihd + J)
        index = 2 * J * ihd * (seq_len + 1) / 2
        attend = 4 * hd * self.n_heads * self.selected_keys(seq_len)
        experts = d * self.n_experts + self.top_k * 3 * d * self.d_ff
        return self.n_layers * (2 * (proj + experts) + index + attend) + 2 * d * self.vocab_size

    def num_params(self) -> int:
        d, E = self.d_model, self.n_experts
        layer = self._attention_params() + d * E + E + self.n_held * 3 * d * self.d_ff + 2 * d
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.vocab_size * d + self.n_layers * layer + d + head


# Kwai-Keye/Keye-VL-2.0-30B-A3B config.json, the language model's keys (the
# catalog's row): 48 layers alike, GQA 32 / 4 at heads of 128 under the
# indexer's selection, then 128 experts of width 768, 8 a token, no shared one
KEYE_VL_2_30B_A3B = KeyeConfig(
    vocab_size=151936, d_model=2048, n_layers=48, n_heads=32, n_kv_heads=4, d_ff=768,
    max_seq=262144, rope_theta=1e7, rms_eps=1e-6, tie_embeddings=False,
    n_experts=128, top_k=8, norm_topk_prob=True, qk_norm=False,
    router_aux_coeff=0.0, router_z_coeff=0.0, router_score="softmax", selection_bias=True,
    head_dim=128, indexer_heads=16, indexer_head_dim=64, indexer_topk=2048, index_chunk=512,
)
# small, the sequence past `indexer_topk` so that the selection bites, two chunks of
# queries of which the first lies wholly before the cut
KEYE_TINY = dataclasses.replace(
    KEYE_VL_2_30B_A3B, vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=32, max_seq=128, remat=False, n_experts=8, top_k=2, head_dim=16,
    indexer_heads=4, indexer_head_dim=8, indexer_topk=16, index_chunk=16,
)


def attention_axes() -> Params:
    """Logical axes of the leaves `attention_params` makes. The indexer
    and the norms are small and stay whole."""
    return {
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "q_norm": ("layers", "norm"),
        "k_norm": ("layers", "norm"),
        "idx_wq": ("layers", "embed", None),
        "idx_wk": ("layers", "embed", None),
        "idx_ww": ("layers", "embed", None),
        "idx_norm_w": ("layers", "norm"),
        "idx_norm_b": ("layers", "norm"),
    }


def attention_params(config: KeyeConfig, keys: jax.Array) -> Params:
    """The sublayer's weights of every layer, stacked (drawn from `keys[0]`)."""
    c = config
    L, d, hd = c.n_layers, c.d_model, c.head_dim
    J, ihd = c.indexer_heads, c.indexer_head_dim
    keys = jax.random.split(keys[0], 7)

    def per_layer(k, shape):
        return jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype))(jax.random.split(k, L))

    return {
        "wq": per_layer(keys[0], (d, c.n_heads * hd)),
        "wk": per_layer(keys[1], (d, c.n_kv_heads * hd)),
        "wv": per_layer(keys[2], (d, c.n_kv_heads * hd)),
        "wo": per_layer(keys[3], (c.n_heads * hd, d)),
        "q_norm": jnp.ones((L, hd), c.param_dtype),
        "k_norm": jnp.ones((L, hd), c.param_dtype),
        "idx_wq": per_layer(keys[4], (d, J * ihd)),
        "idx_wk": per_layer(keys[5], (d, ihd)),
        "idx_ww": per_layer(keys[6], (d, J)),
        "idx_norm_w": jnp.ones((L, ihd), c.param_dtype),
        "idx_norm_b": jnp.zeros((L, ihd), c.param_dtype),
    }


# -- the selection ---------------------------------------------------------------


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> uint32 in the floats' own order (-0.0 with 0.0), so the
    k-th largest can be found bit by bit."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    signed = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(signed, jnp.uint32) ^ jnp.uint32(0x80000000)


def select_keys(scores: jax.Array, visible: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """scores [..., n] float32, visible [..., n] bool -> (selected [..., n]
    bool, ties [...] bool): a row's visible keys where it has at most `k`,
    else its `k` visible keys of largest score, equal scores to the lower
    index; `ties`: rows whose cut fell on equal scores.

    Exact, without a sort: the k-th largest of a row is the largest
    threshold that at least k of its scores reach, built from the top
    bit down over the scores read as ordered integers (32 counts)."""
    u = jnp.where(visible, _ordered(scores), jnp.uint32(0))

    def reached(t):
        return jnp.sum(u >= t[..., None], axis=-1, dtype=jnp.int32)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(reached(cand) >= k, cand, t)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))[..., None]
    above, equal = visible & (u > kth), visible & (u == kth)
    few = jnp.sum(visible, axis=-1, dtype=jnp.int32) <= k
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)  # of the equal ones, this many
    ties = ~few & (jnp.sum(equal, axis=-1, dtype=jnp.int32) > room)

    def lower_first(equal):
        return equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= room[..., None])

    # a running count over the row, taken only where some row needs it
    equal = jax.lax.cond(jnp.any(ties), lower_first, lambda equal: equal, equal)
    return jnp.where(few[..., None], visible, above | equal), ties


def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    x = x.astype(_F32)
    mean = x.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(_F32) + b.astype(_F32)


def _inv_freq(dim: int, theta: float) -> jax.Array:
    return 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=_F32) / dim)


def selection(x: jax.Array, lp: Params, c: KeyeConfig, positions: jax.Array) -> tuple:
    """x [B, S, D] -> (the packed selection int32 [B, S, selection blocks x 128],
    the selected pairs (int32), the rows whose cut fell on equal scores
    (int32)). Nothing here takes a gradient."""
    B, S, D = x.shape
    J, ihd, k, dt = c.indexer_heads, c.indexer_head_dim, c.indexer_topk, x.dtype
    chunk = min(c.index_chunk, S)
    x = jax.lax.stop_gradient(x)
    lp = {n: jax.lax.stop_gradient(lp[n])
          for n in ("idx_wq", "idx_wk", "idx_ww", "idx_norm_w", "idx_norm_b")}
    rows, cols = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    starts = range(0, S, chunk)
    if S > k:
        with jax.named_scope("dsa.index.proj"):
            cos, sin = rope_tables(positions, _inv_freq(ihd, c.rope_theta))
            q_i = jnp.einsum("bsd,djh->bjsh", x, lp["idx_wq"].astype(dt).reshape(D, J, ihd))
            q_i = rotate_head_major(q_i, cos, sin)
            k_i = _layer_norm(jnp.einsum("bsd,dh->bsh", x, lp["idx_wk"].astype(dt)),
                              lp["idx_norm_w"], lp["idx_norm_b"], c.indexer_norm_eps)
            k_i = rotate_head_major(k_i.astype(dt)[:, None], cos, sin)[:, 0]
            w_i = jnp.einsum("bsd,dj->bsj", x.astype(_F32), lp["idx_ww"].astype(_F32),
                             precision=jax.lax.Precision.HIGHEST) / math.sqrt(J * ihd)
    packed, n_selected, n_ties = [], jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
    for lo in starts:
        hi = min(lo + chunk, S)  # the chunk's rows see the keys before `hi`
        causal = jnp.broadcast_to((rows[lo:hi] >= cols[:, :hi])[None], (B, hi - lo, hi))
        if hi <= k:  # every row of the chunk has at most k keys: all of them
            chosen = causal
        else:
            with jax.named_scope("dsa.index.scores"):
                # the batch a BATCH dimension of the product (the module's docstring)
                s = jax.lax.dot_general(q_i[:, :, lo:hi], k_i[:, :hi],
                                        (((3,), (2,)), ((0,), (0,))),
                                        preferred_element_type=_F32)  # [B, J, rows, keys]
                weight = jnp.swapaxes(w_i[:, lo:hi], 1, 2)[..., None]
                index = jnp.sum(jnp.maximum(s, 0.0) * weight, axis=1)
            with jax.named_scope("dsa.select"):
                chosen, ties = select_keys(index, causal, k)
                n_ties += jnp.sum(ties, dtype=jnp.int32)
        with jax.named_scope("dsa.select"):
            n_selected += jnp.sum(chosen, dtype=jnp.int32)
            packed.append(pack_selection(jnp.pad(chosen, ((0, 0), (0, 0), (0, S - hi)))))
    with jax.named_scope("dsa.select"):
        return jnp.concatenate(packed, axis=1), n_selected, n_ties


def dsa_sublayer(x: jax.Array, lp: Params, c: KeyeConfig, *, positions: jax.Array,
                 segment_ids: Optional[jax.Array]) -> tuple[jax.Array, Params]:
    """x = RMSNorm(hidden) [B, S, D] -> (what the sublayer adds to the
    hidden state [B, S, D], {"dsa_selected", "dsa_ties"}: the selected
    (query, key) pairs and the queries whose cut fell on equal scores).
    The equations and how they run are the module's docstring."""
    if segment_ids is not None:
        raise NotImplementedError("packed sequences (segment_ids) under the indexer's selection")
    if positions.ndim != 1:
        raise NotImplementedError("positions by batch row under the indexer's selection")
    B, S, D = x.shape
    H, hd, dt = c.n_heads, c.head_dim, x.dtype
    with obs.layer_span("dsa.attn"):  # counts engaged sites, while tracing
        with jax.named_scope("dsa.qkv"):
            q, k, v = (head_major(jnp.einsum("bsd,dnh->bnsh", x,
                                             lp[n].astype(dt).reshape(D, -1, hd)))
                       for n in ("wq", "wk", "wv"))
        with jax.named_scope("dsa.norm"):
            q, k = rms_norm(q, lp["q_norm"], c.rms_eps), rms_norm(k, lp["k_norm"], c.rms_eps)
        with jax.named_scope("dsa.rope"):
            cos, sin = rope_tables(positions, _inv_freq(hd, c.rope_theta))
            q, k = rotate_head_major(q, cos, sin), rotate_head_major(k, cos, sin)
        sel, n_selected, n_ties = selection(x, lp, c, positions)
        # kept for the backward by the remat policy (llama._remat), not made again
        sel = jax.ad_checkpoint.checkpoint_name(sel, "dsa_sel")
        with jax.named_scope("dsa.attend"):
            o = attention_head_major(q, k, v, causal=True, impl=c.attention_impl, selection=sel)
            # saved by the "dots" remat policy, as models/gqa.py's is
            o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        with jax.named_scope("dsa.out"):
            out = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(dt).reshape(H, hd, D))
    return out, {"dsa_selected": n_selected, "dsa_ties": n_ties}
