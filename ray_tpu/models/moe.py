"""Sparse expert feed-forward layer (Mixtral, OLMoE, ZAYA1, GLM-4.7-Flash, Laguna) for the one decoder.

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

Two things a configuration may ask of the layer beside that (ZAYA1,
models/cca.py, asks both):

  * `router_kind` "mlp" (arXiv:2511.17127): the stream is projected down
    to `router_hidden`, the PREVIOUS layer's router state is added at a
    learned scale (so `moe_ffn` takes that state and hands on its own:
    the layer scan carries it beside the hidden state), and a small MLP
    gives the logits; the expert is chosen by probability plus a
    selection bias that is no part of the weight;
  * `experts_held`: this chip's SHARE of an expert-parallel deployment.
    The parameters hold experts `first_expert_held ..  + experts_held`
    only; the router still routes over all `n_experts`; pairs of held
    experts are sorted first and multiplied, pairs routed elsewhere
    contribute zero to the output and to every gradient (they are not
    dropped pairs: another chip computes them) and are counted apart.
    No code stands in for the absent chips or their exchange.

And two that GLM-4.7-Flash (models/mla.py) asks, in the DeepSeek-V3 form
(arXiv:2412.19437, arXiv:2408.15664):

  * `router_score` "sigmoid": each expert's score is the sigmoid of its
    logit, by itself; the `top_k` experts with the largest score PLUS a
    selection bias (`router_bias`, which takes no gradient and is no
    part of the weight) are chosen; the weights are the chosen scores
    themselves, renormalised over the chosen (`norm_topk_prob`) and
    multiplied by `routed_scaling`;
  * `shared_d_ff`: a shared expert of that width, a dense SwiGLU every
    token runs, added to the routed sum. It is computed whole on every
    chip of a deployment (and counted once where shares are added up),
    under the named scope `shared.ffn`: it is no part of `moe.*`.

Laguna (models/laguna.py) asks the same of a SOFTMAX router: top-10 of
256 probabilities chosen by probability + the layer's `router_bias`
(where the layer's parameters carry one), renormalised over the chosen
and multiplied by `routed_scaling`, beside a shared expert.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu import obs
from ray_tpu.models import llama
from ray_tpu.nn.layers import init_dense, rms_norm, swiglu
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
    # "linear": one [d_model, n_experts] matrix. "mlp": down to
    # `router_hidden`, plus the previous layer's state, then an MLP (ZAYA1)
    router_kind: str = "linear"
    router_hidden: int = 0
    # "softmax" over all the experts' logits, or "sigmoid" of each by
    # itself, chosen by score + `router_bias` (GLM-4.7-Flash; linear router)
    router_score: str = "softmax"
    routed_scaling: float = 1.0  # on the routed experts' weights, either score
    shared_d_ff: int = 0         # width of the shared expert (0: none)
    # this chip's share: experts first_expert_held .. + experts_held of
    # n_experts are in the parameters (None: all of them)
    experts_held: Optional[int] = None
    first_expert_held: int = 0

    @property
    def n_held(self) -> int:
        return self.n_experts if self.experts_held is None else self.experts_held

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires: the dense decoder's count
        with the `top_k` experts a token runs and the router in place of
        the one MLP."""
        dense_mlp = 2 * self.d_model * self.d_ff * 3
        routed = (self.top_k * dense_mlp + 2 * self.d_model * self.n_experts
                  + 2 * self.d_model * self.shared_d_ff * 3)
        return super().flops_per_token(seq_len) + self.n_layers * (routed - dense_mlp)

    def num_params(self) -> int:
        d, f, E, r = self.d_model, self.d_ff, self.n_experts, self.router_hidden
        router = d * E if self.router_kind == "linear" else d * r + 2 * r * r + r * E + 2 * r + E
        if self.router_score == "sigmoid":
            router += E  # the selection bias
        # the experts held here + the shared expert + router
        ffn = self.n_held * 3 * d * f + 3 * d * self.shared_d_ff + router
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


def expert_axes(config: Optional[MoEConfig] = None) -> Params:
    """Logical axes of the leaves `expert_params` makes."""
    axes = {
        "w_gate": ("layers", "expert", "embed", "mlp"),
        "w_up": ("layers", "expert", "embed", "mlp"),
        "w_down": ("layers", "expert", "mlp", "embed"),
    }
    if config is not None and config.shared_d_ff:
        axes.update(shared_gate=("layers", "embed", "mlp"), shared_up=("layers", "embed", "mlp"),
                    shared_down=("layers", "mlp", "embed"))
    if config is None or config.router_kind == "linear":
        if config is not None and config.router_score == "sigmoid":
            axes["router_bias"] = ("layers", "expert")
        return {"router": ("layers", "embed", "expert"), **axes}
    return {
        "router_down": ("layers", "embed", None),
        "router_gamma": ("layers", "norm"),
        "router_norm": ("layers", "norm"),
        "router_w1": ("layers", None, None),
        "router_w2": ("layers", None, None),
        "router_w3": ("layers", None, "expert"),
        "router_bias": ("layers", "expert"),
        **axes,
    }


def expert_params(config: MoEConfig, key: jax.Array) -> Params:
    """Router and expert weights of every layer, stacked over layers."""
    c = config
    L, E = c.n_layers, c.n_experts
    held = slice(c.first_expert_held, c.first_expert_held + c.n_held)
    keys = jax.random.split(key, 4)

    def per_expert(k, shape):  # distinct init per (layer, expert); a share holds its own experts'
        ks = jax.random.split(k, L * E).reshape(L, E)
        if c.experts_held is not None:
            ks = ks[:, held]
        return jax.vmap(jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype)))(ks)

    def per_layer(k, shape):
        return jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype))(jax.random.split(k, L))

    if c.router_kind == "linear":
        router = {"router": per_layer(keys[0], (c.d_model, E))}
        if c.router_score == "sigmoid":
            router["router_bias"] = jnp.zeros((L, E), c.param_dtype)
    else:
        r = c.router_hidden
        k_down, k1, k2, k3 = jax.random.split(keys[0], 4)
        router = {
            "router_down": per_layer(k_down, (c.d_model, r)),
            "router_gamma": jnp.ones((L, r), c.param_dtype),
            "router_norm": jnp.ones((L, r), c.param_dtype),
            "router_w1": per_layer(k1, (r, r)),
            "router_w2": per_layer(k2, (r, r)),
            "router_w3": per_layer(k3, (r, E)),
            "router_bias": jnp.zeros((L, E), c.param_dtype),
        }
    if c.shared_d_ff:
        k_gate, k_up, k_down = jax.random.split(jax.random.fold_in(key, 7), 3)
        router.update(shared_gate=per_layer(k_gate, (c.d_model, c.shared_d_ff)),
                      shared_up=per_layer(k_up, (c.d_model, c.shared_d_ff)),
                      shared_down=per_layer(k_down, (c.shared_d_ff, c.d_model)))
    return {
        **router,
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


def _mlp_router_logits(xt, lp: Params, c: MoEConfig, r_prev):
    """ZAYA1's router (arXiv:2511.17127), all in float32 at `highest`:
    r = xt W_down + gamma * r_prev (the previous layer's r, nothing
    before the first layer); logits = W_3 gelu(W_2 gelu(W_1 RMSNorm(r))).
    -> (logits [N, E], r [N, router_hidden])."""
    f32 = lambda name: lp[name].astype(jnp.float32)  # noqa: E731
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    r = dot(xt.astype(jnp.float32), f32("router_down"))
    if r_prev is not None:
        r = r + f32("router_gamma") * r_prev
    y = rms_norm(r, f32("router_norm"), c.rms_eps)
    y = jax.nn.gelu(dot(y, f32("router_w1")), approximate=False)
    y = jax.nn.gelu(dot(y, f32("router_w2")), approximate=False)
    return dot(y, f32("router_w3")), r


def moe_ffn(x: jax.Array, lp: Params, c: MoEConfig,
            router_state: Optional[jax.Array] = None) -> tuple[jax.Array, Params, Any]:
    """Dropless top-k expert FFN.

    x [B, S, D] -> (out [B, S, D], statistics of this layer, the
    router's state [B, S, router_hidden] for the next layer's
    `router_state`: None for a linear router). The statistics:
    `tokens_per_expert` int32 [E] over ALL `n_experts` (its sum is N *
    top_k); `dropped_pairs`: chosen (token, expert) pairs that the
    routing lost, counted in no expert's group (0: the layer is
    dropless); `imbalance` (largest over mean of `tokens_per_expert`);
    `balance_loss` (E * sum_e f_e * P_e with f_e the share of tokens
    that chose e among their top_k and P_e the mean router probability)
    and `z_loss` (mean of logsumexp(router logits)^2), both unweighted.
    A configuration that holds a share (`experts_held`) adds
    `pairs_elsewhere`: pairs routed to experts another chip holds, which
    are counted in `tokens_per_expert`, multiplied by nothing here and
    contribute zero; N * top_k less it is the rows the grouped matmuls
    really multiplied. With `top_k` > 1 a token may have some of its
    pairs here and some elsewhere: each PAIR is held or elsewhere by its
    own expert, the held ones sorted first, and `dropped_pairs` stays 0.
    A sigmoid router's `balance_loss` takes a token's scores as shares
    of their sum, and its `z_loss` is 0. The shared expert
    (`shared_d_ff`) is no pair and is in none of the counts: every token
    runs it, here, whole, whatever share of the routed experts is held.

    The router reads the compute-dtype stream but multiplies, takes its
    softmax and chooses in float32 (`highest`: a float32 matmul is one
    bf16 pass on the chip otherwise).
    """
    B, S, D = x.shape
    E, K = c.n_experts, c.top_k
    N = B * S
    first, held = c.first_expert_held, c.n_held
    if not 0 < held <= E - first:
        raise ValueError(f"experts {first} .. {first + held} held of {E}")
    xt = x.reshape(N, D)
    with obs.layer_span("moe.ffn"):  # counts engaged sites, while tracing
        with jax.named_scope("moe.router"):
            if c.router_kind == "mlp":
                prev = None if router_state is None else router_state.reshape(N, -1)
                logits, router_state = _mlp_router_logits(xt, lp, c, prev)
                router_state = router_state.reshape(B, S, -1)
            else:
                logits = jnp.einsum(
                    "nd,de->ne", xt.astype(jnp.float32), lp["router"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
            if c.router_score == "sigmoid":
                # chosen by score + bias; weighted by the scores alone, renormalised
                # over the chosen and scaled (arXiv:2412.19437, eq. 12-16)
                scores = jax.nn.sigmoid(logits)
                _, chosen = jax.lax.top_k(scores + lp["router_bias"].astype(jnp.float32), K)
                w = jnp.take_along_axis(scores, chosen, axis=-1)
                if c.norm_topk_prob:
                    w = w / (w.sum(-1, keepdims=True) + 1e-20)
                w = w * c.routed_scaling
                # for the balance statistic: the scores as shares of a token's total;
                # a sigmoid router has no partition function to hold down
                probs, lse = scores / scores.sum(-1, keepdims=True), jnp.zeros((N,), jnp.float32)
            else:
                lse = jax.nn.logsumexp(logits, axis=-1)
                probs = jnp.exp(logits - lse[:, None])  # [N, E]
                if c.router_kind == "mlp" or "router_bias" in lp:
                    # chosen by probability + bias; weighted by the probability alone
                    # (ZAYA1's MLP router; a linear one whose layer carries a selection
                    # bias: Laguna, models/laguna.py)
                    _, chosen = jax.lax.top_k(probs + lp["router_bias"].astype(jnp.float32), K)
                    w = jnp.take_along_axis(probs, chosen, axis=-1)
                else:
                    w, chosen = jax.lax.top_k(probs, K)     # [N, K]
                if c.norm_topk_prob:
                    w = w / w.sum(-1, keepdims=True)
                if c.routed_scaling != 1.0:
                    w = w * c.routed_scaling
            flat = chosen.reshape(N * K)
            counts = jnp.sum(flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :],
                             axis=0, dtype=jnp.int32)
            balance = E * jnp.sum(counts.astype(jnp.float32) / N * probs.mean(0))
            z = jnp.mean(jnp.square(lse))
        with jax.named_scope("moe.dispatch"):
            pairs = jnp.arange(N * K, dtype=jnp.int32)
            sizes, tail = counts, held < E
            if tail:
                # the held experts' pairs first, in the experts' order; the rest
                # follow the last group, where no tile of a grouped matmul goes
                flat = (flat - first) % E
                sizes = counts[first:first + held]
            _, order = jax.lax.sort((flat, pairs), num_keys=1, is_stable=True)
            _, inv = jax.lax.sort((order, pairs), num_keys=1)
            inv = inv.reshape(N, K)
            xs = _to_expert_order(xt, order, inv)
        with jax.named_scope("moe.experts"):
            # named for the remat policy (llama._decoder), which knows
            # dot_general's outputs but not a grouped matmul's
            name = jax.ad_checkpoint.checkpoint_name
            gmm = functools.partial(grouped_matmul, group_sizes=sizes, tail=tail)
            gate = name(gmm(xs, lp["w_gate"].astype(x.dtype)), "moe_gate")
            up = name(gmm(xs, lp["w_up"].astype(x.dtype)), "moe_up")
            # the router's weight goes on BEFORE the down projection (the
            # same sum): the backward then needs no output of `w_down`,
            # so that matmul is not run again to differentiate the weights
            act = (jax.nn.silu(gate) * up).astype(jnp.float32)
            act = (act * _pair_weights(w, order, inv)[:, None]).astype(x.dtype)
            ys = gmm(act, lp["w_down"].astype(x.dtype))
        with jax.named_scope("moe.combine"):
            out = _to_token_order(ys, order, inv)
    stats = {
        "tokens_per_expert": counts,
        "dropped_pairs": N * K - counts.sum(),
        "imbalance": counts.max() / (N * K / E),
        "balance_loss": balance,
        "z_loss": z,
    }
    if tail:
        stats["pairs_elsewhere"] = N * K - sizes.sum()
    out = out.reshape(B, S, D)
    if c.shared_d_ff:
        with jax.named_scope("shared.ffn"):
            out = out + swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return out, stats, router_state
