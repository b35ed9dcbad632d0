"""Sparse expert feed-forward layer (Mixtral, OLMoE) for the one decoder.

The reference has NO expert parallelism (SURVEY.md §2.4 — absent from
python/ray/llm); this is a native capability. This module holds what an
expert configuration adds to the llama-family decoder: `MoEConfig`, the
expert layer `moe_ffn`, its parameters and their logical axes. The
block, the layer scan, the head and the loss are models/llama.py's,
which calls `moe_ffn` in place of the dense SwiGLU when its
configuration is a `MoEConfig`.

The layer is DROPLESS: every chosen (token, expert) pair is computed,
none is padded to a capacity. The N * top_k pairs are sorted by expert
(a stable sort, so a group keeps token order), the tokens' rows are
gathered into that order, gate / up / down run as grouped matmuls over
the ragged groups, each row weighted by the router on the way, and the
rows go back to token order, where a token's are summed. Both
permutations are gathers, forward and backward (custom VJPs below).

Which kernel multiplies is `ops/grouped_matmul.py`'s to say, from what
it can observe; this module calls `grouped_matmul` three times and
knows nothing of the choice. On a TPU with no multi-device mesh it is
the Pallas kernels there (`ragged-dot-tiled*` in a profile, tiles
chosen from the shapes: the one-chip training cell). Everywhere else it
is `jax.lax.ragged_dot`: on the CPU, and under a mesh, where the expert
dimension carries the logical axis "expert", which the sharding rules
map to the mesh `ep` axis, and the partitioner places the grouped
matmuls (XLA's own 512 x 512 x 512 Mosaic kernel on a TPU; speed under
a mesh is not measured yet).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import llama
from ray_tpu.nn.layers import init_dense
from ray_tpu.ops.grouped_matmul import grouped_matmul

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig(llama.LlamaConfig):
    """`d_ff` is the width of ONE expert."""

    n_experts: int = 8
    top_k: int = 2
    # the chosen experts' weights renormalised to sum to 1 (Mixtral) or
    # left as the softmax over all experts gave them (OLMoE)
    norm_topk_prob: bool = True
    # RMSNorm with a learned scale over the whole projected q and k,
    # before the head split and rotary (OLMoE; leaves q_norm, k_norm)
    qk_norm: bool = False
    router_aux_coeff: float = 0.01  # load-balancing loss weight
    router_z_coeff: float = 0.0     # router z-loss weight

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires: the dense decoder's count
        with the `top_k` experts a token runs and the router in place of
        the one MLP."""
        dense_mlp = 2 * self.d_model * self.d_ff * 3
        routed = self.top_k * dense_mlp + 2 * self.d_model * self.n_experts
        return super().flops_per_token(seq_len) + self.n_layers * (routed - dense_mlp)

    def num_params(self) -> int:
        d, f, E = self.d_model, self.d_ff, self.n_experts
        ffn = E * 3 * d * f + d * E  # experts + router
        qk = d + self.n_kv_heads * self.head_dim if self.qk_norm else 0
        return super().num_params() + self.n_layers * (ffn + qk - 3 * d * f)


MOE_TINY = MoEConfig(
    vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq=128, remat=False, n_experts=4, top_k=2,
)
MIXTRAL_8X7B = MoEConfig(
    vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq=32768, rope_theta=1e6, n_experts=8, top_k=2,
)
# allenai/OLMoE-1B-7B-0125-Instruct config.json; what the config lacks is
# from the OLMoE paper (arXiv:2409.02060) and the HF `olmoe` model code
OLMOE_1B_7B = MoEConfig(
    vocab_size=50304, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=16,
    d_ff=1024, max_seq=4096, rope_theta=10000.0, rms_eps=1e-5,
    n_experts=64, top_k=8, norm_topk_prob=False, qk_norm=True,
    router_aux_coeff=0.01, router_z_coeff=0.001,
)


def expert_axes() -> Params:
    """Logical axes of the leaves `expert_params` makes."""
    return {
        "router": ("layers", "embed", "expert"),
        "w_gate": ("layers", "expert", "embed", "mlp"),
        "w_up": ("layers", "expert", "embed", "mlp"),
        "w_down": ("layers", "expert", "mlp", "embed"),
    }


def expert_params(config: MoEConfig, key: jax.Array) -> Params:
    """Router and expert weights of every layer, stacked over layers."""
    c = config
    L, E = c.n_layers, c.n_experts
    keys = jax.random.split(key, 4)

    def per_expert(k, shape):  # distinct init per (layer, expert)
        ks = jax.random.split(k, L * E).reshape(L, E)
        return jax.vmap(jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype)))(ks)

    return {
        "router": jax.vmap(lambda kk: init_dense(kk, (c.d_model, E), c.param_dtype))(
            jax.random.split(keys[0], L)),
        "w_gate": per_expert(keys[1], (c.d_model, c.d_ff)),
        "w_up": per_expert(keys[2], (c.d_model, c.d_ff)),
        "w_down": per_expert(keys[3], (c.d_ff, c.d_model)),
    }


# -- the two permutations, as gathers in both directions ---------------------
#
# `order` [N*K]: the flat pair (token * K + choice) at each row of expert
# order; `inv` [N, K]: the row of each pair. Each is the other's inverse,
# and each function below is the other's transpose: AD's own transpose of
# a gather is a scatter-add, which serialises on the chip.


def _rows_of_pairs(xt, order, inv):
    return xt[order // inv.shape[1]]


def _sum_of_pairs(y, inv):
    return y[inv].astype(jnp.float32).sum(axis=1).astype(y.dtype)


@jax.custom_vjp
def _to_expert_order(xt, order, inv):
    """xt [N, D] -> [N*K, D]: the token's row for every pair, pairs
    sorted by expert."""
    return _rows_of_pairs(xt, order, inv)


def _to_expert_order_fwd(xt, order, inv):
    return _rows_of_pairs(xt, order, inv), inv


def _to_expert_order_bwd(inv, g):
    with jax.named_scope("moe.dispatch"):
        return _sum_of_pairs(g, inv), None, None


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)


@jax.custom_vjp
def _to_token_order(y, order, inv):
    """y [N*K, D] in expert order -> [N, D]: each token's K rows, summed
    in float32."""
    return _sum_of_pairs(y, inv)


def _to_token_order_fwd(y, order, inv):
    return _sum_of_pairs(y, inv), (order, inv)


def _to_token_order_bwd(res, g):
    order, inv = res
    with jax.named_scope("moe.combine"):
        return _rows_of_pairs(g, order, inv), None, None


_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


@jax.custom_vjp
def _pair_weights(w, order, inv):
    """w [N, K] -> [N*K]: each pair's router weight, in expert order."""
    return w.reshape(-1)[order]


def _pair_weights_fwd(w, order, inv):
    return w.reshape(-1)[order], inv


def _pair_weights_bwd(inv, g):
    return g[inv], None, None


_pair_weights.defvjp(_pair_weights_fwd, _pair_weights_bwd)


def moe_ffn(x: jax.Array, lp: Params, c: MoEConfig) -> tuple[jax.Array, Params]:
    """Dropless top-k expert FFN.

    x [B, S, D] -> (out [B, S, D], statistics of this layer):
    `tokens_per_expert` int32 [E] (its sum is N * top_k),
    `dropped_pairs` (pairs that reached no group: 0), `imbalance`
    (largest over mean of `tokens_per_expert`), `balance_loss`
    (E * sum_e f_e * P_e with f_e the share of tokens that chose e among
    their top_k and P_e the mean router probability) and `z_loss`
    (mean of logsumexp(router logits)^2), both unweighted.

    The router reads the compute-dtype stream but multiplies, takes its
    softmax and chooses in float32 (`highest`: a float32 matmul is one
    bf16 pass on the chip otherwise).
    """
    B, S, D = x.shape
    E, K = c.n_experts, c.top_k
    N = B * S
    xt = x.reshape(N, D)
    with obs.layer_span("moe.ffn"):  # counts engaged sites, while tracing
        with jax.named_scope("moe.router"):
            logits = jnp.einsum(
                "nd,de->ne", xt.astype(jnp.float32), lp["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            lse = jax.nn.logsumexp(logits, axis=-1)
            probs = jnp.exp(logits - lse[:, None])  # [N, E]
            w, chosen = jax.lax.top_k(probs, K)     # [N, K]
            if c.norm_topk_prob:
                w = w / w.sum(-1, keepdims=True)
            flat = chosen.reshape(N * K)
            counts = jnp.sum(flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :],
                             axis=0, dtype=jnp.int32)
            balance = E * jnp.sum(counts.astype(jnp.float32) / N * probs.mean(0))
            z = jnp.mean(jnp.square(lse))
        with jax.named_scope("moe.dispatch"):
            pairs = jnp.arange(N * K, dtype=jnp.int32)
            _, order = jax.lax.sort((flat, pairs), num_keys=1, is_stable=True)
            _, inv = jax.lax.sort((order, pairs), num_keys=1)
            inv = inv.reshape(N, K)
            xs = _to_expert_order(xt, order, inv)
        with jax.named_scope("moe.experts"):
            # named for the remat policy (llama._decoder), which knows
            # dot_general's outputs but not a grouped matmul's
            name = jax.ad_checkpoint.checkpoint_name
            gate = name(grouped_matmul(xs, lp["w_gate"].astype(x.dtype), counts), "moe_gate")
            up = name(grouped_matmul(xs, lp["w_up"].astype(x.dtype), counts), "moe_up")
            # the router's weight goes on BEFORE the down projection (the
            # same sum): the backward then needs no output of `w_down`,
            # so that matmul is not run again to differentiate the weights
            act = (jax.nn.silu(gate) * up).astype(jnp.float32)
            act = (act * _pair_weights(w, order, inv)[:, None]).astype(x.dtype)
            ys = grouped_matmul(act, lp["w_down"].astype(x.dtype), counts)
        with jax.named_scope("moe.combine"):
            out = _to_token_order(ys, order, inv)
    stats = {
        "tokens_per_expert": counts,
        "dropped_pairs": N * K - counts.sum(),
        "imbalance": counts.max() / (N * K / E),
        "balance_loss": balance,
        "z_loss": z,
    }
    return out.reshape(B, S, D), stats
