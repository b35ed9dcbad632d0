"""Sparse expert feed-forward layer (Mixtral, OLMoE, ZAYA1, GLM-4.7-Flash, Laguna, Keye, Nemotron-H) for the one decoder.

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
An expert's FORM is the configuration's (`expert_act`): "swiglu", down(
silu(gate x) * up x), three matrices and nine grouped matmuls a layer
forward and backward, or "relu2" (Nemotron-H, models/nemotron_h.py),
down(relu(up x)^2), two matrices and six: there is then no `w_gate`
(and no `shared_gate`) leaf, and None stands where the gate stood in
every function below, both custom VJPs among them.

Which kernel multiplies is `ops/grouped_matmul.py`'s to say, from what
it can observe; this module calls `grouped_matmul` three times (twice
for experts of two matrices) and knows nothing of the choice. On a TPU with no multi-device mesh it is
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
    WHAT IS SIZED BY WHAT: the router, the two sorts, `inv` and the
    counts see all N * top_k pairs; where the share is small enough
    (`held_rows_bound`: a static bound C on the held rows, from N *
    top_k, the share and nothing else), everything of model or expert
    width after the sort (the gathered rows, gate / up / act / ys, the
    kernels' grids, `_zero_tail`'s selects, the sum back into the
    tokens) has C rows, not N * top_k. A step whose routing puts more
    than C pairs on the held experts runs that block over all the rows
    instead (`jax.lax.cond`, the same mathematics: no pair is lost
    either way), and the statistic `compact` says which ran. The sum of
    the C rows back into the N tokens (`_sum_of_held_rows`: the
    combine's forward, the dispatch's backward) is a product with the
    [N, C] 0/1 matrix where that is small (no cell since PR 63: tiny
    shapes) and, where it is not, the same product over the BAND of that
    matrix where the ones lie, the rows brought into token order first: a
    block of 256 tokens owns one run of rows, and a window of W = 256 C / N
    rows (the slack times the rows a block owns on average: 256 or 512 in
    the seven cells, where 256 x top_k = 1,024-2,560 stood until PR 63)
    from the run's start is the block's sum; a block whose run is longer
    takes as many windows as it needs, counted on the device, so any
    routing is exact and a skewed one only slower (the statistic
    `band_trips`: the most windows a block took, 1 when W was enough).
    Work linear in the tokens. ONE form is built a site, chosen from the
    shapes by the two costs (`_sum_is_linear`); the counters
    `moe.sum.product` / `moe.sum.linear` say which.

And two that GLM-4.7-Flash (models/mla.py) asks, in the DeepSeek-V3 form
(arXiv:2412.19437, arXiv:2408.15664):

  * `router_score` "sigmoid": each expert's score is the sigmoid of its
    logit, by itself; the `top_k` experts with the largest score PLUS a
    selection bias (`router_bias`, which takes no gradient and is no
    part of the weight) are chosen; the weights are the chosen scores
    themselves, renormalised over the chosen (`norm_topk_prob`) and
    multiplied by `routed_scaling`;
  * `shared_d_ff`: a shared expert of that width, a dense SwiGLU (or,
    under `expert_act` "relu2", the routed experts' own form) every
    token runs, added to the routed sum. It is computed whole on every
    chip of a deployment (and counted once where shares are added up),
    under the named scope `shared.ffn`: it is no part of `moe.*`.

Laguna (models/laguna.py) asks the same of a SOFTMAX router: top-10 of
256 probabilities chosen by probability + the layer's `router_bias`
(where the layer's parameters carry one), renormalised over the chosen
and multiplied by `routed_scaling`, beside a shared expert. Keye-VL-2.0's
language model (models/dsa.py) asks it of top-8 of 128 with no shared
expert and no scaling, the bias a leaf of the layer's own parameters
(`selection_bias`).
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
    """`d_ff` is the width of ONE expert (of `expert_matrices` matrices:
    `expert_act`)."""

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
    # a softmax linear router's layer carries a selection bias too: chosen by
    # probability + `router_bias`, weighted by the probability alone (Keye, models/dsa.py)
    selection_bias: bool = False
    routed_scaling: float = 1.0  # on the routed experts' weights, either score
    shared_d_ff: int = 0         # width of the shared expert (0: none)
    # an expert's form, routed and shared alike: "swiglu" = down(silu(gate x) * up x), three
    # matrices; "relu2" = down(relu(up x)^2), two and no `w_gate` leaf (Nemotron-H)
    expert_act: str = "swiglu"
    # this chip's share: experts first_expert_held .. + experts_held of
    # n_experts are in the parameters (None: all of them)
    experts_held: Optional[int] = None
    first_expert_held: int = 0

    @property
    def linear_router_bias(self) -> bool:
        """Whether a LINEAR router's layer carries `router_bias` (an MLP
        router's always does)."""
        return self.router_score == "sigmoid" or self.selection_bias

    @property
    def n_held(self) -> int:
        return self.n_experts if self.experts_held is None else self.experts_held

    @property
    def expert_matrices(self) -> int:
        """Matrices of one expert, by its form (`expert_act`)."""
        return _EXPERT_MATRICES[self.expert_act]

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires: the dense decoder's count
        with the `top_k` experts a token runs (each of the matrices its
        form has) and the router in place of the one MLP."""
        dense_mlp = 2 * self.d_model * self.d_ff * 3
        m = self.expert_matrices
        routed = (self.top_k * 2 * self.d_model * self.d_ff * m + 2 * self.d_model * self.n_experts
                  + 2 * self.d_model * self.shared_d_ff * m)
        return super().flops_per_token(seq_len) + self.n_layers * (routed - dense_mlp)

    def num_params(self) -> int:
        d, f, E, r = self.d_model, self.d_ff, self.n_experts, self.router_hidden
        router = d * E if self.router_kind == "linear" else d * r + 2 * r * r + r * E + 2 * r + E
        if self.linear_router_bias:
            router += E  # the selection bias
        # the experts held here + the shared expert + router
        m = self.expert_matrices
        ffn = self.n_held * m * d * f + m * d * self.shared_d_ff + router
        qk = d + self.n_kv_heads * self.head_dim if self.qk_norm else 0
        return super().num_params() + self.n_layers * (ffn + qk - 3 * d * f)


_EXPERT_MATRICES = {"swiglu": 3, "relu2": 2}


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
    if config is not None and config.expert_act == "relu2":   # two matrices: no gate
        axes = {k: v for k, v in axes.items() if k not in ("w_gate", "shared_gate")}
    if config is None or config.router_kind == "linear":
        if config is not None and config.linear_router_bias:
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
        if c.linear_router_bias:
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
    if c.expert_act == "relu2":   # two matrices an expert, routed and shared: no gate
        router.pop("shared_gate", None)
        return {**router, "w_up": per_expert(keys[2], (c.d_model, c.d_ff)),
                "w_down": per_expert(keys[3], (c.d_ff, c.d_model))}
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


def _activation(gate, up):
    """An expert's hidden row from its first matmuls' outputs: silu(gate)
    * up, or relu(up)^2 where the expert has no gate (`gate` None)."""
    return jnp.square(jax.nn.relu(up)) if gate is None else jax.nn.silu(gate) * up


def _all_rows(xt, w, w_gate, w_up, w_down, order, inv, sizes, *, tail, dtype):
    """The routed experts over ALL N * K pair rows -> [N, D]: what a
    configuration that holds every expert (or a large share) runs, and
    the branch a small share falls back to. `w_gate` None: experts of
    two matrices (`expert_act` "relu2"), four grouped matmuls fewer a
    layer and step."""
    with jax.named_scope("moe.dispatch"):
        xs = _to_expert_order(xt, order, inv)
    with jax.named_scope("moe.experts"):
        # named for the remat policy (llama._remat), which knows
        # dot_general's outputs but not a grouped matmul's
        name = jax.ad_checkpoint.checkpoint_name
        gmm = functools.partial(grouped_matmul, group_sizes=sizes, tail=tail)
        gate = None if w_gate is None else name(gmm(xs, w_gate.astype(dtype)), "moe_gate")
        up = name(gmm(xs, w_up.astype(dtype)), "moe_up")
        # the router's weight goes on BEFORE the down projection (the
        # same sum): the backward then needs no output of `w_down`,
        # so that matmul is not run again to differentiate the weights
        act = _activation(gate, up).astype(jnp.float32)
        act = (act * _pair_weights(w, order, inv)[:, None]).astype(dtype)
        ys = gmm(act, w_down.astype(dtype))
    with jax.named_scope("moe.combine"):
        return _to_token_order(ys, order, inv)


# -- a small share: everything after the sort over a bound on the HELD rows ----
#
# The held experts' pairs are sorted first, so where `sizes.sum() <= C` the
# rows `order[:C]` are every held pair in expert order, then rows of pairs
# routed elsewhere, which lie past the last group: no tile of a grouped
# matmul visits them, `_zero_tail` makes them zero, and a zero row adds
# nothing to its token. `tok` [C] is the token of each of those rows.

# C = _SLACK x the pairs a uniform router would put on the held experts,
# rounded up to the row tile `ops/grouped_matmul.py::pick_tiles` wants. At 2
# the benchmark's share cells sit at 0.50-0.69 of C (`laguna-train` holds
# 3.1-4.3% of its pairs against a uniform 3.125%, `glm47f-train` 11.1-14.2%
# against 12.5%: the configurations' `lr_why`, summed over their blocks; ONE
# block of `laguna-train` crossed C on 2 seeds of 30 inside a 10 s window:
# PERF.md, PR 40); a routing past it is still exact, only slower. A share
# UNDER a thirty-second takes _SLACK_SMALL: the rows that ONE repeated token
# brings (Zipf traffic's first: about 1,190 of a sequence's 8,192, which a
# router sends to an expert whole) are then most of a uniform share, 1,639
# rows at `solar-open2-train-8k`'s 8 of 320, and at 2 two such blocks on the
# held experts crossed C on 3 seeds of 38 inside a 10 s window, 5-19 steps
# of 40 at 0.273 s for 0.251 (PERF.md, PR 60); at 4 it takes five.
_SLACK = 2
_SLACK_SMALL = 4
_ROW_TILE = 512


def held_rows_bound(pairs: int, held: int, experts: int) -> Optional[int]:
    """The static bound C on the rows of a share of `held` of `experts`
    among `pairs` = N * top_k, or None where no compact path is built:
    a share that would not halve the rows (ZAYA1's 8 of 16), or all the
    experts."""
    if held >= experts:
        return None
    uniform = -(-pairs * held // experts)
    slack = _SLACK if 32 * held >= experts else _SLACK_SMALL
    bound = min(pairs, -(-slack * uniform // _ROW_TILE) * _ROW_TILE)
    return bound if 2 * bound <= pairs else None


def _fits(sizes, bound):
    """Whether this step's held pairs lie in the first `bound` rows: the
    branch's predicate, forward and backward, and the statistic `compact`."""
    return sizes.sum() <= bound


# The sum of the held rows into their tokens, y [C, D] -> [N, D], is the forward
# of `moe.combine` and the backward of `moe.dispatch`. It has two forms and ONE
# is built a site, chosen from the shapes while tracing (`_sum_is_linear`).


def _sum_where_equal(tokens, of, rows):
    """rows [R, D] -> float32 [T, D]: row r summed into the token `of[r]` names, by a
    product with the [T, R] 0/1 matrix of (token, row) on the MXU: the products are
    exact and the sum is float32; the caller rounds it to the rows' type, once."""
    hot = (tokens[:, None] == of[None, :]).astype(rows.dtype)
    exact = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    return jnp.dot(hot, rows, precision=exact, preferred_element_type=jnp.float32)


def _sum_by_product(y, tok, pairs):
    """That product over all N tokens and C rows: 2 N C D operations for a sum
    of C rows, at the MXU's rate (93-97% of it), and quadratic in the tokens."""
    return _sum_where_equal(jnp.arange(pairs[0], dtype=tok.dtype), tok, y).astype(y.dtype)


# tokens a window's product sums into: of 128 / 256 / 512 the best alone at
# `keye-train-8k`'s shape (1.000 / 0.851 / 1.167 ms) and within 3% of it at
# `glm47f-train`'s (0.388 / 0.401 / 0.546), with ONE window of 256 x top_k rows a
# block (PERF.md, PR 44; not taken again under PR 63's windows)
_BAND_TOKENS = 256
# a window is a multiple of the MXU's 128 rows. Of 128 / 256 / 384 / 512 alone, ms a call
# on a uniform router's rows (PR 63, step (0)): 1.828 / 1.754 / 1.676 / 1.664 at
# `mellum2-train-16k`'s shape (a block owns 256 rows on average), 0.563 / 0.536 / 0.507 /
# 0.505 at `keye-train-8k`'s (256), 0.261 / 0.247 / 0.276 / 0.302 at `glm47f-train`'s (128),
# 0.402 / 0.452 / 0.514 / 0.558 at `solar-open2-train-8k`'s (51): the slack times the
# average run, rounded up, is the best or within 0.05 ms of it at every shape
_BAND_ROWS = 128


def _band(n_tokens: int, top_k: int, rows: int) -> tuple[int, int]:
    """(tokens a block, rows a window W). With the rows in token order a block's
    rows are one run; the C rows bound a STEP's held rows by the slack
    (`held_rows_bound`), so block x C / N bounds an average BLOCK's by the same
    slack: that is the window, rounded up to the MXU's rows, and never more than
    block x top_k (every pair of the block held) or C."""
    block = min(_BAND_TOKENS, n_tokens)
    mean = -(-block * rows // n_tokens)
    return block, min(block * top_k, rows, -(-mean // _BAND_ROWS) * _BAND_ROWS)


def _runs(tok, n_tokens: int, block: int):
    """-> (first row, rows) of each block of `block` tokens, with the rows in token
    order: a block's run starts after the rows of every token before it. Rows that
    name no token (`_held_rows`: N) are in no run."""
    edges = jnp.minimum(jnp.arange(0, n_tokens + block, block, dtype=tok.dtype), n_tokens)
    ends = jnp.sum(tok[None, :] < edges[:, None], axis=1, dtype=tok.dtype)
    return ends[:-1], ends[1:] - ends[:-1]


def _band_trips(tok, pairs, rows: int):
    """The most windows any block's run takes (1: every run fits its window)."""
    block, window = _band(*pairs, rows)
    return jnp.maximum(-(-_runs(tok, pairs[0], block)[1].max() // window), 1)


def _sum_by_band(y, tok, pairs):
    """The same product over the BAND where its ones lie. With the rows in
    token order (a sort of C keys and one gather of C rows) a block of
    tokens owns one contiguous run of rows, so windows of `_band`'s W rows
    from where the run starts, as many as the run is long (ONE, unless the
    routing gave the block more than the slack times its share: the trip count
    is read on the device), summed into the block's tokens in float32 and
    rounded once are the block's sum: about 2 N W D operations and N W / 256
    rows read, both linear in the tokens. The last window reaches into the next
    blocks' runs, whose rows name no token of this block and add nothing, and
    past row C into W rows gathered for that and named for no token: a window is
    never pushed back over rows an earlier one summed."""
    (n_tokens, top_k), (rows, width) = pairs, y.shape
    block, window = _band(n_tokens, top_k, rows)
    toks, perm = jax.lax.sort((tok, jnp.arange(rows, dtype=tok.dtype)), num_keys=1)
    ys = y[jnp.pad(perm, (0, window))]
    toks = jnp.pad(toks, (0, window), constant_values=n_tokens)
    starts, runs = _runs(tok, n_tokens, block)
    firsts = jnp.arange(0, n_tokens, block, dtype=tok.dtype)   # a block's first token

    def sum_of_block(at):
        start, first, trips = at
        tokens = first + jnp.arange(block, dtype=tok.dtype)

        def add_window(j, total):
            row = start + j * window
            return total + _sum_where_equal(
                tokens, jax.lax.dynamic_slice(toks, (row,), (window,)),
                jax.lax.dynamic_slice(ys, (row, 0), (window, width)))

        total = jax.lax.fori_loop(0, trips, add_window, jnp.zeros((block, width), jnp.float32))
        return total.astype(y.dtype)

    # a loop, not a batch: the window is read where the product wants it and never kept
    out = jax.lax.map(sum_of_block, (starts, firsts, -(-runs // window)))
    return out.reshape(-1, width)[:n_tokens]


# Measured alone on a v5e, bf16, ms a call with the sort and the gather in it, a uniform
# router's rows (PERF.md section 6, PR 63, step (0); (N, top_k, C, D) -> W): the product /
# the band with ONE window of 256 x top_k rows a block (PR 44's) / the band as built:
#   (16384, 8, 32768, 2304) -> 512  `mellum2-train-16k`     12.900 / 2.600 / 1.664
#   (16384, 8, 32768, 2048) -> 512  `sdar-train-8k`         11.503 / 2.368 / 1.513
#   (8192, 8, 16384, 2048)  -> 512  `keye-train-8k`          2.850 / 0.714 / 0.505
#   (8192, 8, 6656, 4096)   -> 256  `solar-open2-train-8k`   2.330 / 0.886 / 0.452
#   (8192, 6, 6144, 2688)   -> 256  `twotower-train-8k`      1.425 / 0.491 / 0.282
#   (8192, 4, 8192, 2048)   -> 256  `glm47f-train`           1.445 / 0.324 / 0.247
#   (4096, 10, 2560, 3072)  -> 256  `laguna-train`           0.334 / 0.387 / 0.155
# (`laguna-train`'s one window was all of C, the product in a loop, so it built the
# product until PR 63). The sort, the gather into token order and the runs are 0.04-0.12 ms
# of a call up to C = 16,384 and 0.89-1.00 at 32,768, where the gather passes 300 MB; a
# block whose run takes a second window adds the window's 5-8 us, a block of 256 x top_k
# rows 0.02-0.03 ms to a call. The product ran at 97-98% of the MXU's 197 TFLOP/s; the band
# moved the bytes reckoned below at 310-546 GB/s, slowest at the two largest shapes.
_PRODUCT_FLOPS = 0.95 * 197e12
_BAND_BYTES_PER_S = 310e9


def _sum_is_linear(n_tokens: int, top_k: int, rows: int, width: int, itemsize: int) -> bool:
    """Whether the band sums C = `rows` rows of `width` into N tokens sooner
    than the product, from the two costs the shapes give: the product's
    2 N C D operations at the MXU's rate, the band's bytes (a window a block,
    the rows gathered into token order and written, the sums written) at
    the rate it was measured to move them."""
    product_s = 2 * n_tokens * rows * width / _PRODUCT_FLOPS
    block, window = _band(n_tokens, top_k, rows)
    windows = -(-n_tokens // block) * window
    band_s = (windows + 2 * rows + n_tokens) * width * itemsize / _BAND_BYTES_PER_S
    return band_s < product_s


def _sum_of_held_rows(y, tok, pairs):
    """y [C, D], tok [C] -> [N, D]: each token's rows summed in float32 and
    rounded once to y's type. `pairs` = (N, top_k): the tokens, and the most
    rows one of them can have among the C."""
    linear = _sum_is_linear(*pairs, *y.shape, y.dtype.itemsize)
    # counted while tracing, as `moe.compact` is: the form a site was BUILT with
    with obs.layer_span("moe.sum.linear" if linear else "moe.sum.product"):
        return (_sum_by_band if linear else _sum_by_product)(y, tok, pairs)


def _rows_of_tokens(xt, tok):
    """xt[tok], a row past the held pairs (`tok` = N there) reading the last token's:
    no tile of a grouped matmul visits it and `_zero_tail` makes what comes of it zero."""
    return xt.at[tok].get(mode="clip")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _to_held_rows(xt, tok, pairs):
    """xt [N, D] -> [C, D]: the token's row for each of the first C rows
    of expert order (any row where `tok` names no token: `_rows_of_tokens`)."""
    return _rows_of_tokens(xt, tok)


def _to_held_rows_fwd(xt, tok, pairs):
    return _rows_of_tokens(xt, tok), tok


def _to_held_rows_bwd(pairs, tok, g):
    with jax.named_scope("moe.dispatch"), jax.named_scope("moe.held"):
        return _sum_of_held_rows(g, tok, pairs), None


_to_held_rows.defvjp(_to_held_rows_fwd, _to_held_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _from_held_rows(y, tok, pairs):
    """y [C, D] in expert order -> [N, D]: the held rows summed into
    their tokens, in float32. `_to_held_rows`'s transpose."""
    return _sum_of_held_rows(y, tok, pairs)


def _from_held_rows_fwd(y, tok, pairs):
    return _sum_of_held_rows(y, tok, pairs), tok


def _from_held_rows_bwd(pairs, tok, g):
    with jax.named_scope("moe.combine"), jax.named_scope("moe.held"):
        return _rows_of_tokens(g, tok), None


_from_held_rows.defvjp(_from_held_rows_fwd, _from_held_rows_bwd)


@jax.custom_vjp
def _held_pair_weights(w, rows, inv):
    """w [N, K] -> [C]: the router weight of the pair at each of the
    first C rows of expert order (`rows` = order[:C])."""
    return w.reshape(-1)[rows]


def _held_pair_weights_fwd(w, rows, inv):
    return w.reshape(-1)[rows], inv


def _held_pair_weights_bwd(inv, g):
    # a pair whose row is past C reads the zero appended at C
    bound = g.shape[0]
    return jnp.append(g, jnp.zeros((1,), g.dtype))[jnp.minimum(inv, bound)], None, None


_held_pair_weights.defvjp(_held_pair_weights_fwd, _held_pair_weights_bwd)


def _held_gate_up(xt, w_gate, w_up, tok, pairs, sizes):
    """-> gate, up [C, d_ff] of the held rows (gate None where the
    experts have none)."""
    with jax.named_scope("moe.dispatch"), jax.named_scope("moe.held"):
        xs = _to_held_rows(xt, tok, pairs)
    with jax.named_scope("moe.experts"), jax.named_scope("moe.held"):
        gmm = functools.partial(grouped_matmul, group_sizes=sizes, tail=True)
        return None if w_gate is None else gmm(xs, w_gate), gmm(xs, w_up)


def _held_down_sum(gate, up, w, w_down, rows, inv, tok, sizes):
    """gate, up [C, d_ff] -> [N, D]: weighted, down, summed into the tokens."""
    with jax.named_scope("moe.experts"), jax.named_scope("moe.held"):
        act = _activation(gate, up).astype(jnp.float32)
        act = (act * _held_pair_weights(w, rows, inv)[:, None]).astype(up.dtype)
        ys = grouped_matmul(act, w_down, sizes, tail=True)
    with jax.named_scope("moe.combine"), jax.named_scope("moe.held"):
        return _from_held_rows(ys, tok, inv.shape)


def _held_rows(bound, order, inv, sizes):
    """-> (the pair, the token) of each of the first C rows of expert order. Past the
    held pairs a row names N, which is no token: it sorts after every token's rows
    and is in no block's run (`_sum_by_band`), and no product sums it."""
    rows = order[:bound]
    held = jnp.arange(bound, dtype=sizes.dtype) < sizes.sum()
    return rows, jnp.where(held, rows // inv.shape[1], inv.shape[0])


def _held_or_all_fwd(bound, xt, w, w_gate, w_up, w_down, order, inv, sizes):
    """-> (out [N, D], gate and up [C, d_ff] for the backward: zeros
    where the block ran over all rows, which keeps nothing: its backward
    runs gate and up again)."""
    def held():
        rows, tok = _held_rows(bound, order, inv, sizes)
        gate, up = _held_gate_up(xt, w_gate, w_up, tok, inv.shape, sizes)
        return _held_down_sum(gate, up, w, w_down, rows, inv, tok, sizes), gate, up

    def every():
        with jax.named_scope("moe.all"):
            out = _all_rows(xt, w, w_gate, w_up, w_down, order, inv, sizes,
                            tail=True, dtype=xt.dtype)
        kept = jnp.zeros((bound, w_up.shape[2]), xt.dtype)
        return out, None if w_gate is None else kept, kept

    with jax.named_scope("moe.experts"):  # the `cond`'s own time and its predicate's
        return jax.lax.cond(_fits(sizes, bound), held, every)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_or_all(bound, xt, w, w_gate, w_up, w_down, order, inv, sizes):
    """The routed experts of a small share -> [N, D]: over the first
    `bound` rows of expert order where the held pairs fit in them, over
    all N * K rows where they do not. ONE custom VJP whose forward and
    backward each branch, so that what is kept between them is what both
    branches share (the arguments) and the held rows' gate and up
    [C, d_ff]: differentiating a `cond` of two differentiable bodies
    would keep the UNION of their residuals, [N * K, d_ff] zeros in the
    branch that ran compact."""
    return _held_or_all_fwd(bound, xt, w, w_gate, w_up, w_down, order, inv, sizes)[0]


def _held_or_all_vjp_fwd(bound, xt, w, w_gate, w_up, w_down, order, inv, sizes):
    out, gate, up = _held_or_all_fwd(bound, xt, w, w_gate, w_up, w_down, order, inv, sizes)
    # named for the remat policy, as `_all_rows` names its own
    name = jax.ad_checkpoint.checkpoint_name
    return out, (xt, w, w_gate, w_up, w_down, order, inv, sizes,
                 name(gate, "moe_gate"), name(up, "moe_up"))


def _held_or_all_vjp_bwd(bound, res, g):
    xt, w, w_gate, w_up, w_down, order, inv, sizes, gate, up = res

    def held():
        rows, tok = _held_rows(bound, order, inv, sizes)
        # each stage's forward is traced for its transpose and dies unused: the
        # kept gate and up stand in for the first's, the second's `ys` feeds nothing
        _, down_sum_t = jax.vjp(
            lambda gate, up, w, w_down: _held_down_sum(gate, up, w, w_down, rows, inv, tok, sizes),
            gate, up, w, w_down)
        d_gate, d_up, d_w, d_down = down_sum_t(g)
        _, gate_up_t = jax.vjp(
            lambda xt, w_gate, w_up: _held_gate_up(xt, w_gate, w_up, tok, inv.shape, sizes),
            xt, w_gate, w_up)
        d_xt, d_gate_w, d_up_w = gate_up_t((d_gate, d_up))
        return d_xt, d_w, d_gate_w, d_up_w, d_down

    def every():
        with jax.named_scope("moe.all"):
            _, all_rows_t = jax.vjp(
                lambda *a: _all_rows(*a, order, inv, sizes, tail=True, dtype=xt.dtype),
                xt, w, w_gate, w_up, w_down)
            return all_rows_t(g)

    with jax.named_scope("moe.experts"):
        grads = jax.lax.cond(_fits(sizes, bound), held, every)
    return (*grads, None, None, None)


_held_or_all.defvjp(_held_or_all_vjp_fwd, _held_or_all_vjp_bwd)
# one trace and one lowering a step program for the blocks of one shape, not one a block:
# two bodies, forward and backward, are host seconds of every start (`setup_s`)
_held_or_all_once = jax.jit(_held_or_all, static_argnums=(0,))


def _of_chosen(values, chosen):
    """values [N, E], chosen int [N, K] -> values[n, chosen[n, k]] [N, K]:
    `jnp.take_along_axis(values, chosen, axis=-1)` value for value and
    gradient for gradient (one element selected and zeros added to it:
    exact), as a compare and a sum over the E columns and not a gather.
    On the chip a gather of single elements fetches them one by one and
    its transpose scatters them one by one: 1.34 ms for the 131,072 of
    `mellum2-train-16k`'s [16384, 64] and 0.89 ms back, where the
    router's matmul takes 0.09 (PERF.md section 6, PR 59). Either
    direction of this is one fused pass over [N, K, E]. With ONE expert
    a token (ZAYA1) the gather stays: 8,192 single elements are 0.08 ms
    and their scatter nothing, and without it the compiler packs
    `zaya1-train`'s step, the cell nearest the device's memory, 0.08 GiB
    worse (8.112 -> 8.190 GiB of temporaries: rehearsal, PR 59)."""
    if chosen.shape[1] == 1:
        return jnp.take_along_axis(values, chosen, axis=-1)
    picked = chosen[:, :, None] == jnp.arange(values.shape[1], dtype=chosen.dtype)
    return jnp.sum(jnp.where(picked, values[:, None, :], 0.0), axis=-1)


def _mlp_router_logits(xt, lp: Params, c: MoEConfig, r_prev):
    """ZAYA1's router (arXiv:2511.17127), all in float32 at `highest`
    (the first product reads the bfloat16 stream as it stands, as
    `moe_ffn`'s does; the three after it multiply two float32 operands):
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
    Where the share is small enough for a bound C on its rows
    (`held_rows_bound`, from N * top_k, the share and nothing else) the
    block is BUILT with two bodies and adds `compact` (int32): 1 where
    this step's held pairs fitted in C rows and everything after the
    sort ran over C rows, 0 where they did not and it ran over all
    N * top_k; and `band_trips` (int32): the most windows any block of
    256 tokens took in the sum of the held rows into their tokens (`_band`;
    1 where every block's run of rows fitted the window the shapes give, 0
    where the step ran over all rows). The router, the sorts and every
    statistic come before the branch and see all pairs either way.
    A sigmoid router's `balance_loss` takes a token's scores as shares
    of their sum, and its `z_loss` is 0. The shared expert
    (`shared_d_ff`) is no pair and is in none of the counts: every token
    runs it, here, whole, whatever share of the routed experts is held.

    The router reads the compute-dtype stream but multiplies, takes its
    softmax and chooses in float32 (`highest`: a float32 matmul is one
    bf16 pass on the chip otherwise). The cast of a bfloat16 stream
    costs nothing there: at `highest` the compiler multiplies the rows
    AS THEY STAND by the float32 weight's three bfloat16 terms, three
    passes and no split of the rows, at the speed the rows are read
    (PERF.md section 6, PR 59: 0.084 ms at [16384, 2304] x [2304, 64],
    and the same split spelled by hand, with `reduce_precision`, 0.083;
    spelled with `astype` the compiler drops the round trip as excess
    precision and multiplies ONE term). What a router that weighs by a
    score it did not choose by (score + bias chose) pays for is the
    pick of the chosen scores, which is `_of_chosen` and no gather.
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
                w = _of_chosen(scores, chosen)
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
                    w = _of_chosen(probs, chosen)
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
        bound = held_rows_bound(N * K, held, E)
        # counted while tracing: a site BUILT with the compact path, or without
        with obs.layer_span("moe.full" if bound is None else "moe.compact"):
            if bound is None:
                out = _all_rows(xt, w, lp.get("w_gate"), lp["w_up"], lp["w_down"], order, inv,
                                sizes, tail=tail, dtype=x.dtype)
            else:
                with jax.named_scope("moe.experts"):
                    # (an expert of two matrices has no `w_gate`: None all the way down)
                    weights = [None if k not in lp else lp[k].astype(x.dtype)
                               for k in ("w_gate", "w_up", "w_down")]
                out = _held_or_all_once(bound, xt, w, *weights, order, inv, sizes)
    stats = {
        "tokens_per_expert": counts,
        "dropped_pairs": N * K - counts.sum(),
        "imbalance": counts.max() / (N * K / E),
        "balance_loss": balance,
        "z_loss": z,
    }
    if tail:
        stats["pairs_elsewhere"] = N * K - sizes.sum()
    if bound is not None:
        fits = _fits(sizes, bound)
        trips = _band_trips(_held_rows(bound, order, inv, sizes)[1], inv.shape, bound)
        stats.update(compact=fits.astype(jnp.int32),
                     band_trips=jnp.where(fits, trips, 0).astype(jnp.int32))
    out = out.reshape(B, S, D)
    if c.shared_d_ff:
        with jax.named_scope("shared.ffn"):
            if c.expert_act == "relu2":
                hidden = jnp.einsum("bsd,df->bsf", x, lp["shared_up"].astype(x.dtype))
                out = out + jnp.einsum("bsf,fd->bsd", jnp.square(jax.nn.relu(hidden)),
                                       lp["shared_down"].astype(x.dtype))
            else:
                out = out + swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return out, stats, router_state
