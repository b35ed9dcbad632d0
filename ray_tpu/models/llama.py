"""Llama-family decoder, TPU-first.

Design (vs reference, which delegates all model execution to
torch/vLLM inside workers — e.g. python/ray/llm/_internal/serve/
deployments/llm/vllm/vllm_engine.py): pure-functional jax with

  * stacked layer params + `lax.scan` over layers (one compiled block,
    fast compiles, pipeline-parallel ready: the "layers" dim reshapes to
    ("stage", "layers_per_stage") and shards over the mesh `pp` axis),
  * logical-axis annotations on every tensor (ray_tpu.parallel.sharding)
    so DP/FSDP/TP/SP all come from the rules table, not model edits,
  * bf16 compute / fp32 params+norms, fp32 softmax and loss,
  * per-layer rematerialization (`jax.checkpoint`) to trade MXU FLOPs
    for HBM.

The attention sublayer is a row of `MIXERS`, the kind the configuration's
class names (`LlamaConfig.mixer`): a module a kind, the llama family's
own models/gqa.py, which holds the layout paragraph. Leading DENSE layers
before a stack of expert layers and a multi-token-prediction block after
it (`first_dense_layers`, `mtp_layers`) are this module's too.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
from functools import partial
from typing import Any, ClassVar, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu.nn.layers import fused_cross_entropy_loss, init_dense, rms_norm, swiglu
from ray_tpu.parallel.context import current_mesh

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # compute/activation dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # remat granularity: "full" recomputes the whole block in backward
    # (max memory savings, ~1 extra forward of MXU work); "dots" saves
    # matmul outputs and recomputes only elementwise/attention-score work
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) — near
    # no-remat throughput at a fraction of full-activation memory.
    remat_policy: str = "dots"
    attention_impl: str = "xla"
    tie_embeddings: bool = False
    # the kind of the attention sublayer, a key of `MIXERS`: the class's, not a field
    mixer: ClassVar[str] = "gqa"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def flops_per_token(self, seq_len: int) -> float:
        """Forward FLOPs a token requires in a sequence of `seq_len`: 2
        per matmul parameter on the token path (projections, MLP, head)
        plus causal attention, QK^T and PV at 2 * head_dim per (query,
        key) pair and head each, (seq_len + 1) / 2 keys a query on
        average. Training is 3x this; recompute is not counted."""
        d, f, L = self.d_model, self.d_ff, self.n_layers
        hd = self.head_dim
        attn_proj = 2 * d * (self.n_heads * hd + 2 * self.n_kv_heads * hd + self.n_heads * hd)
        scores = 4 * hd * self.n_heads * (seq_len + 1) / 2
        mlp = 2 * d * f * 3
        emb = 2 * d * self.vocab_size
        return L * (attn_proj + scores + mlp) + emb

    def num_params(self) -> int:
        d, f, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab_size
        hd = self.head_dim
        per_layer = d * hd * (self.n_heads * 2 + self.n_kv_heads * 2) + 3 * d * f + 2 * d
        head = 0 if self.tie_embeddings else d * V
        return V * d + L * per_layer + d + head


LLAMA3_8B = LlamaConfig()
LLAMA3_1B = LlamaConfig(
    d_model=2048, n_layers=16, n_heads=32, n_kv_heads=8, d_ff=8192, tie_embeddings=True
)
LLAMA_400M = LlamaConfig(
    vocab_size=32000, d_model=1024, n_layers=24, n_heads=16, n_kv_heads=8, d_ff=2816,
    max_seq=2048,
)
LLAMA_TINY = LlamaConfig(
    vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq=128, remat=False,
)


def _moe(config: LlamaConfig):
    """models/moe.py when the configuration has experts (a `MoEConfig`),
    else None. Imported here: that module builds on this one, and a
    dense configuration never loads it."""
    if not hasattr(config, "n_experts"):
        return None
    from ray_tpu.models import moe

    return moe


@dataclasses.dataclass(frozen=True)
class Mixer:
    """What the stack needs to know of one kind of attention sublayer. Its
    module (loaded for a configuration of the kind, never for another's)
    has `attention_axes()`, `attention_params(c, keys)` and the sublayer,
    `(x, lp, c, *, positions, segment_ids, **once)` -> what it adds to the
    hidden state, or that and the layer's statistics."""

    module: str
    sublayer: str
    # `module.<once>(c)` -> keyword arguments of the sublayer made ONCE, outside the layer scan
    once: Optional[str] = None
    # the rings of parallel/tp_overlap.py are its, and with it the dense FFN's: under tp > 1
    # its sublayer is told `overlap=True` (another kind's matmuls are the partitioner's)
    tp_rings: bool = False
    # the scope of its last matmul, which the residual add fuses into and is traced under
    # (None: under the stack's own name)
    adds_under: Optional[str] = None
    # what `_remat` saves of it by name: the flash kernels' output and log-sum-exp, which
    # are no dot_general's, and what else its backward reads
    saves: tuple = ("attn_out", "attn_lse")

    def load(self):
        return importlib.import_module(self.module)


MIXERS = {
    "gqa": Mixer("ray_tpu.models.gqa", "gqa_sublayer", once="rotary_tables", tp_rings=True,
                 adds_under="attn.out"),
    "cca": Mixer("ray_tpu.models.cca", "cca_sublayer"),
    "mla": Mixer("ray_tpu.models.mla", "mla_sublayer"),
    # dsa_sel: the packed selection, which the backward reads and nothing computes twice
    "dsa": Mixer("ray_tpu.models.dsa", "dsa_sublayer", saves=("attn_out", "attn_lse", "dsa_sel")),
}
# saved too, and no mixer's. tp_rs_out: a row-parallel matmul's output where
# parallel/tp_overlap.py sums it (there the dot the first policy sees is only one chip's
# product); moe_gate, moe_up: the expert layer's first two grouped matmuls (models/moe.py),
# which are no dot_general either
_NO_MIXER_SAVES = ("tp_rs_out", "moe_gate", "moe_up")


def _mixer(config: LlamaConfig) -> Mixer:
    """The row of the configuration's kind: the seam at which the block's
    attention sublayer is chosen, at trace time."""
    try:
        return MIXERS[config.mixer]
    except KeyError:
        raise ValueError(f"unknown mixer kind {config.mixer!r}; one of {sorted(MIXERS)}") from None


def _own_stack(config: LlamaConfig):
    """The module that builds the tree and runs the layers of a
    configuration whose layers differ in kind within one stack
    (`layer_types`), else None: models/laguna.py (sliding-window and full
    attention at unlike head counts over an expert layer, a scan over
    whole periods of kinds) or models/olmo_hybrid.py (gated-delta-rule
    linear attention and full attention over a dense SwiGLU). The
    configuration names its module (`stack_module`), which has
    `logical_axes(c)`, `init_params(c, key)`, `trunk(params, tokens, c,
    positions=, segment_ids=)` and `REMAT_SAVES` (the names its own
    kernels write for `_remat`); the head and the loss stay here."""
    if not hasattr(config, "layer_types"):
        return None
    return importlib.import_module(config.stack_module)


def _block_diffusion(config: LlamaConfig):
    """models/block_diffusion.py when the configuration is trained by
    block diffusion (`diffusion_block` not 0: a clean and a noised copy of
    every sequence under a mask of blocks, a weighted denoising loss), else
    None: the seam at which the step's OBJECTIVE is chosen. The layers stay
    the configuration's own."""
    if not getattr(config, "diffusion_block", 0):
        return None
    from ray_tpu.models import block_diffusion

    return block_diffusion


def _carries_router_state(config: LlamaConfig) -> bool:
    """An MLP router adds the previous layer's state to its own: the
    layer scan then carries (hidden state, router state)."""
    return getattr(config, "router_kind", "linear") == "mlp"


DENSE_FFN_AXES = {"w_gate": ("layers", "embed", "mlp"), "w_up": ("layers", "embed", "mlp"),
                  "w_down": ("layers", "mlp", "embed")}


def stacked_dense(key: jax.Array, n: int, shape: tuple, dtype, scale=None) -> jax.Array:
    """`n` fan-in-initialised matrices of `shape`, each from a key of its own, stacked."""
    return jax.vmap(lambda k: init_dense(k, shape, dtype, scale))(jax.random.split(key, n))


def _beside_the_stack(config: LlamaConfig) -> tuple[int, int]:
    """(leading dense layers before the stack of expert layers, multi-token-
    prediction blocks after it) of a configuration with the fields, whatever
    its attention: `first_dense_layers` (a SwiGLU of width `dense_d_ff`
    under the same attention) and `mtp_layers` (`mtp_loss_weight`)."""
    n_mtp = getattr(config, "mtp_layers", 0)
    if n_mtp not in (0, 1):
        raise ValueError(f"{n_mtp} multi-token-prediction blocks: 0 or 1 are implemented")
    return getattr(config, "first_dense_layers", 0), n_mtp


def _stack_config(c: LlamaConfig, n_layers: int) -> LlamaConfig:
    """`n_layers` blocks of the expert-layer kind and nothing beside them:
    what `logical_axes` and `init_params` build as one stack."""
    return dataclasses.replace(c, n_layers=n_layers, first_dense_layers=0, mtp_layers=0)


def logical_axes(config: LlamaConfig) -> Params:
    """Pytree (parallel to params) of logical-axis tuples."""
    if _own_stack(config) is not None:
        return _own_stack(config).logical_axes(config)
    n_dense, n_mtp = _beside_the_stack(config)
    if n_dense or n_mtp:
        axes = logical_axes(_stack_config(config, config.n_layers - n_dense))
        if n_dense:
            axes["dense_layers"] = {
                "ln1": ("layers", "norm"), **_mixer(config).load().attention_axes(),
                "ln2": ("layers", "norm"), **DENSE_FFN_AXES}
        if n_mtp:
            block = {k: v[1:] for k, v in axes["layers"].items() if k != "router_bias"}
            axes["mtp"] = {"enorm": ("norm",), "hnorm": ("norm",), "eh_proj": (None, "embed"),
                           "block": block, "final_norm": ("norm",)}
        return axes
    layer = {"ln1": ("layers", "norm"), **_mixer(config).load().attention_axes(),
             "ln2": ("layers", "norm")}
    moe = _moe(config)
    if moe is None:
        layer.update(DENSE_FFN_AXES)
    else:
        layer.update(moe.expert_axes(config))
        if config.qk_norm:
            layer.update(q_norm=("layers", "norm"), k_norm=("layers", "norm"))
    axes: Params = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("norm",),
    }
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(config: LlamaConfig, key: jax.Array) -> Params:
    """The tree. `params["layers"]` is the stack the layer scan runs over.
    With layers beside it (`_beside_the_stack`; `n_layers` counts the dense
    and the expert layers together, as `num_hidden_layers` does):
    `params["dense_layers"]`, the leading blocks, run before the scan;
    `params["mtp"]`, two norms, the [2 d_model, d_model] merge, one more
    block of the stack's kind and its own final norm. The selection
    biases of EVERY expert block, the MTP block's last, are one table,
    `params["layers"]["router_bias"]` [expert layers + 1, n_experts]: the
    one state of the model that a balancing rule moves and no gradient
    does, and whoever balances it (chipbench's model builder sets it
    once, before the first step) writes one array."""
    c = config
    if _own_stack(c) is not None:
        return _own_stack(c).init_params(c, key)
    n_dense, n_mtp = _beside_the_stack(c)
    if n_dense or n_mtp:
        return _with_layers_beside(c, key, n_dense, n_mtp)
    keys = jax.random.split(key, 8)
    hd, L = c.head_dim, c.n_layers
    moe = _moe(c)
    if moe is None:
        ffn = {
            "w_gate": stacked_dense(keys[5], L, (c.d_model, c.d_ff), c.param_dtype),
            "w_up": stacked_dense(keys[6], L, (c.d_model, c.d_ff), c.param_dtype),
            "w_down": stacked_dense(keys[7], L, (c.d_ff, c.d_model), c.param_dtype),
        }
    else:
        ffn = moe.expert_params(c, keys[5])
        if c.qk_norm:
            ffn["q_norm"] = jnp.ones((L, c.n_heads * hd), c.param_dtype)
            ffn["k_norm"] = jnp.ones((L, c.n_kv_heads * hd), c.param_dtype)
    attn = _mixer(c).load().attention_params(c, keys[1:5])
    params: Params = {
        "embed": init_dense(keys[0], (c.vocab_size, c.d_model), c.param_dtype, scale=1.0),
        "layers": {
            "ln1": jnp.ones((L, c.d_model), c.param_dtype),
            **attn,
            "ln2": jnp.ones((L, c.d_model), c.param_dtype),
            **ffn,
        },
        "final_norm": jnp.ones((c.d_model,), c.param_dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = init_dense(
            jax.random.fold_in(key, 99), (c.d_model, c.vocab_size), c.param_dtype
        )
    return params


def _with_layers_beside(c: LlamaConfig, key: jax.Array, nd: int, n_mtp: int) -> Params:
    """`init_params`: the expert layers' stack as a stack alone is made,
    the `nd` dense layers and the MTP module."""
    d, dt = c.d_model, c.param_dtype
    k_dense, k_mtp, k_merge = jax.random.split(jax.random.fold_in(key, 47), 3)
    params = init_params(_stack_config(c, c.n_layers - nd), key)
    if nd:
        k_attn, k_gate, k_up, k_down = jax.random.split(k_dense, 4)
        params["dense_layers"] = {
            "ln1": jnp.ones((nd, d), dt),
            # (the first of the attention's keys: a kind that draws one a matrix wants three more)
            **_mixer(c).load().attention_params(_stack_config(c, nd), k_attn[None]),
            "ln2": jnp.ones((nd, d), dt),
            "w_gate": stacked_dense(k_gate, nd, (d, c.dense_d_ff), dt),
            "w_up": stacked_dense(k_up, nd, (d, c.dense_d_ff), dt),
            "w_down": stacked_dense(k_down, nd, (c.dense_d_ff, d), dt),
        }
    if n_mtp:
        block = init_params(_stack_config(c, 1), k_mtp)["layers"]
        bias = block.pop("router_bias")
        params["layers"]["router_bias"] = jnp.concatenate(
            [params["layers"]["router_bias"], bias])
        params["mtp"] = {
            "enorm": jnp.ones((d,), dt), "hnorm": jnp.ones((d,), dt),
            "eh_proj": init_dense(k_merge, (2 * d, d), dt),
            "block": jax.tree.map(lambda w: w[0], block), "final_norm": jnp.ones((d,), dt),
        }
    return params


def packed_positions(segment_ids: Optional[jax.Array], seq_len: int) -> jax.Array:
    """RoPE positions: arange normally; restart at 0 per segment when packing."""
    if segment_ids is None:
        return jnp.arange(seq_len, dtype=jnp.int32)
    idx = jnp.arange(seq_len, dtype=jnp.int32)[None, :]  # [1, S]
    changed = jnp.concatenate(
        [
            jnp.zeros_like(segment_ids[:, :1], dtype=bool),
            segment_ids[:, 1:] != segment_ids[:, :-1],
        ],
        axis=1,
    )
    seg_start = jax.lax.cummax(jnp.where(changed, idx, 0), axis=1)  # [B, S]
    return idx - seg_start


def _block(
    carry,  # h [B, S, D]; (h, router state [B, S, R]) for an MLP router
    lp: Params,  # one layer's params (no leading layer dim)
    *,
    config: LlamaConfig,
    positions: jax.Array,
    segment_ids: Optional[jax.Array],
    once: Optional[dict] = None,
    dense_ffn: bool = False,
) -> tuple[Any, Optional[Params]]:
    """One decoder layer -> (carry, the layer's statistics): the
    attention sublayer of the configuration's kind (its row of `MIXERS`;
    `once`: what the row's module made outside the layer scan; a
    sublayer's own statistics join the layer's), then the dense SwiGLU
    or the expert layer (models/moe.py, whose statistics come back; None
    for a dense layer) by the configuration's own kind; `dense_ffn`: one
    of an expert configuration's leading dense layers. (A configuration
    whose layers differ in kind within the stack, models/laguna.py or
    models/olmo_hybrid.py, has a block of its own beside this one.)"""
    c = config
    moe, mixer = None if dense_ffn else _moe(c), _mixer(c)
    carries_router = _carries_router_state(c)
    h, router_state = carry if carries_router else (carry, None)
    # Under a mesh with tp > 1 the residual stream h stays sharded over
    # `tp` along the tokens and the four matmul sites gather and scatter
    # it inside themselves (parallel/tp_overlap.py); otherwise, and
    # always in llama_decode.py, the plain einsums. (The rings are a
    # kind's by its row and the dense MLP's with it: the expert layer's
    # matmuls are the partitioner's to place.)
    mesh = current_mesh()
    overlap = mixer.tp_rings and mesh is not None and mesh.shape.get("tp", 1) > 1
    if overlap:
        from ray_tpu.parallel.tp_overlap import ag_matmul, rs_matmul

    with jax.named_scope("block.norm"):
        x = rms_norm(h, lp["ln1"], c.rms_eps)
    told = {"overlap": True} if overlap else {}
    y = getattr(mixer.load(), mixer.sublayer)(
        x, lp, c, positions=positions, segment_ids=segment_ids, **(once or {}), **told)
    y, selected = y if isinstance(y, tuple) else (y, {})
    with jax.named_scope(mixer.adds_under) if mixer.adds_under else contextlib.nullcontext():
        h = h + y

    with jax.named_scope("block.norm"):
        x = rms_norm(h, lp["ln2"], c.rms_eps)
    if moe is not None:
        y, stats, router_state = moe.moe_ffn(x, lp, c, router_state)
        stats = {**stats, **selected}
        return ((h + y, router_state) if carries_router else h + y), stats
    with jax.named_scope("dense.ffn"):
        if not overlap:
            return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        # the MLP treats all tokens alike: it can keep the ring's own order
        gate, up = ag_matmul(
            x, (lp["w_gate"].astype(x.dtype), lp["w_up"].astype(x.dtype)), token_order=False)
        return h + rs_matmul(jax.nn.silu(gate) * up, lp["w_down"].astype(x.dtype)), None


def hidden_states(
    params: Params,
    tokens: jax.Array,  # [B, S] int32
    config: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence forward up to the final norm -> h [B, S, D].

    The training loss pairs this with nn.layers.fused_cross_entropy_loss
    so the [T, V] logits never exist as a stored fp32 tensor; serving
    keeps using forward() -> logits."""
    h, _, _ = _trunk(params, tokens, config, positions=positions, segment_ids=segment_ids)
    return rms_norm(h, params["final_norm"], config.rms_eps)


def remat_saves(c: LlamaConfig) -> set:
    """The names `_remat`'s "dots" policy saves: every row's of `MIXERS`, no
    mixer's, and what the kernels of the configuration's own `stack_module`
    write. (A name no program carries changes nothing of it: PERF.md, PR 47.)"""
    stack = _own_stack(c)
    names = {n for row in MIXERS.values() for n in row.saves} | set(_NO_MIXER_SAVES)
    return names if stack is None else names | set(stack.REMAT_SAVES)


def _remat(block, c: LlamaConfig):
    """`block` rematerialised by the configuration's policy."""
    if not c.remat:
        return block
    if c.remat_policy == "dots":
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(*remat_saves(c)),
            ),
        )
    if c.remat_policy == "full":
        return jax.checkpoint(block)
    raise ValueError(f"unknown remat_policy {c.remat_policy!r}; 'full' or 'dots'")


def _trunk(
    params: Params,
    tokens: jax.Array,
    config: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[Params], Any]:
    """The layers, up to the last one's output BEFORE the final norm ->
    (h [B, S, D], the layers' statistics, the rematerialised block the
    stack ran: what a multi-token-prediction module runs once more)."""
    c = config
    B, S = tokens.shape
    if S > c.max_seq:
        raise ValueError(
            f"sequence length {S} exceeds config.max_seq={c.max_seq}; the RoPE "
            "table would silently clamp (JAX OOB gather) — raise max_seq instead"
        )
    if positions is None:
        positions = packed_positions(segment_ids, S)
    if _own_stack(c) is not None:
        # layers of unlike kinds: that module's stack (no block of one kind to hand on)
        return (*_own_stack(c).trunk(params, tokens, c, positions=positions,
                                     segment_ids=segment_ids), None)
    mixer = _mixer(c)
    once = getattr(mixer.load(), mixer.once)(c) if mixer.once else None

    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens]  # [B, S, D]

    block = partial(_block, config=c, positions=positions, segment_ids=segment_ids, once=once)
    # the stack: under this name stand the scan's own slices and stacked writes and
    # the residual adds that no sublayer's scope holds; every scope inside it wins
    with jax.named_scope("block.stack"):
        layers = params["layers"]
        n_dense, n_mtp = _beside_the_stack(c)
        if n_dense or n_mtp:
            # two kinds of block in one model: the leading dense layers run before
            # the scan over the expert layers' stack, which reads ITS rows of the
            # selection-bias table
            layers = {**layers, "router_bias": layers["router_bias"][:c.n_layers - n_dense]}
            h = run_dense_layers(h, params, n_dense, _remat(partial(block, dense_ffn=True), c))
        block = _remat(block, c)

        mesh = current_mesh()
        pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        # an expert configuration is not pipelined: the stages hand on
        # activations only, and its router losses and counts (and an MLP
        # router's state) would be lost on the way; its layers run as the
        # one scan below on any mesh
        if pp > 1 and _moe(c) is None:
            # pipeline the layer stack over the mesh `pp` axis (GPipe
            # microbatch schedule inside this jitted program — see
            # parallel/pipeline.py; reference PP is external vLLM stage
            # actors, vllm_models.py:121)
            if segment_ids is not None:
                raise NotImplementedError("segment packing + pipeline parallelism")
            if positions.ndim > 1:
                # per-batch positions would need microbatching alongside h
                raise NotImplementedError("batched positions + pipeline parallelism")
            from ray_tpu.parallel.pipeline import pipeline_apply, stack_stages

            def stage(stage_params, x):
                out, _ = jax.lax.scan(block, x, stage_params)
                return out

            h, stats = pipeline_apply(
                mesh, stage, stack_stages(layers, pp), h, n_micro=pp
            ), None
        elif _carries_router_state(c):
            # nothing precedes the first layer's router: a state of zeros adds nothing
            state = jnp.zeros((B, S, c.router_hidden), jnp.float32)
            (h, _), stats = jax.lax.scan(block, (h, state), layers)
        else:
            h, stats = jax.lax.scan(block, h, layers)

    return h, stats, block


def run_dense_layers(h: jax.Array, params: Params, n: int, dense_block) -> jax.Array:
    """The `n` leading dense layers, one after another: `dense_block(h,
    layer params) -> (h, None)`, the stack's own block with a dense FFN,
    rematerialised as the stack's (this module's and models/laguna.py's)."""
    for i in range(n):
        h, _ = dense_block(h, jax.tree.map(lambda w: w[i], params["dense_layers"]))
    return h


def mtp_hidden(params: Params, h: jax.Array, next_tokens: jax.Array, c: LlamaConfig,
               block) -> tuple[jax.Array, Params]:
    """The MTP module (arXiv:2412.19437 section 2.2) up to its own final
    norm (which, with the second pass of the head, is
    `loss_and_weight_fn`'s): m_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ;
    RMSNorm_h(h_i)], then the block. h [B, S, D]: the last layer's output
    BEFORE the model's final norm; next_tokens [B, S]: t_{i+1} (the
    batch's targets); `block(h, layer params) -> (h, statistics)`: the
    block of the expert-layer kind, rematerialised as the stack's. ->
    (the block's output, whose logits at i, through the module's own final
    norm and the model's SAME head, predict t_{i+2}; the last position has
    no target and weighs 0; the block's statistics)."""
    mp, d = params["mtp"], c.d_model
    with jax.named_scope("mtp.merge"):
        e = rms_norm(params["embed"].astype(c.dtype)[next_tokens], mp["enorm"], c.rms_eps)
        hn = rms_norm(h, mp["hnorm"], c.rms_eps)
        w = mp["eh_proj"].astype(c.dtype)
        # [e ; hn] W_eh as two products over the two halves of W_eh's rows
        m = (jnp.einsum("bsd,de->bse", e, w[:d]) + jnp.einsum("bsd,de->bse", hn, w[d:]))
    with jax.named_scope("mtp.block"):
        lp = {**mp["block"], "router_bias": params["layers"]["router_bias"][-1]}
        m, stats = block(m, lp)
    return m, stats


def output_weight(params: Params) -> jax.Array:
    """[D, V] lm-head weight (tied embedding transpose when untied absent)."""
    w_out = params.get("lm_head", None)
    if w_out is None:
        w_out = params["embed"].T
    return w_out


def _head_input(h: jax.Array, config: LlamaConfig) -> jax.Array:
    """The normed last hidden state as the head reads it: divided by the
    configuration's `logits_scaling` where it has one (models/granite_hybrid.py:
    logits = h W / 8), here and not on the logits, which the fused loss
    never stores; any other configuration's h as it stands."""
    scaling = getattr(config, "logits_scaling", 1.0)
    return h if scaling == 1.0 else h * (1.0 / scaling)


def forward(
    params: Params,
    tokens: jax.Array,  # [B, S] int32
    config: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence forward -> logits [B, S, V] (loss-dtype fp32 left to caller)."""
    h = hidden_states(
        params, tokens, config, positions=positions, segment_ids=segment_ids
    )
    w_out = output_weight(params)
    return jnp.einsum("bsd,dv->bsv", _head_input(h, config), w_out.astype(config.dtype))


def loss_fn(
    params: Params,
    batch: dict[str, jax.Array],  # tokens [B,S], targets [B,S], optional mask [B,S]
    config: LlamaConfig,
) -> jax.Array:
    return loss_and_weight_fn(params, batch, config)[0]


def loss_and_weight_fn(
    params: Params,
    batch: dict[str, jax.Array],
    config: LlamaConfig,
) -> tuple:
    """(mean_loss, valid_token_count) — the weighted form grad-accum needs.
    An expert configuration adds its two router losses (each the mean
    over layers, at the configuration's coefficients) to the loss and
    returns a third element, the layers' statistics (models/moe.py),
    which train/step.py hands out with the step's metrics. A
    configuration with a multi-token-prediction block (`mtp_hidden`)
    adds that head's loss at its weight; the statistics then carry the
    block's row after the layers' and the two losses apart
    (`loss_main`, `loss_mtp`).

    Uses the fused lm-head + CE (nn/layers.py fused_cross_entropy_loss),
    so the [T, V] fp32 logits and softmax never exist as stored tensors.

    A configuration trained by block diffusion has another objective
    (models/block_diffusion.py: it reads `tokens` alone, and the step
    count train/step.py hands in as `batch["step"]` for its key)."""
    diffusion = _block_diffusion(config)
    if diffusion is not None:
        return diffusion.loss_and_weight(params, batch, config)
    h_last, stats, block = _trunk(
        params, batch["tokens"], config, segment_ids=batch.get("segment_ids")
    )
    with jax.named_scope("head"):
        h = _head_input(rms_norm(h_last, params["final_norm"], config.rms_eps), config)
        loss, weight = fused_cross_entropy_loss(
            h, output_weight(params), batch["targets"], batch.get("mask")
        )
    if stats is None:
        return loss, weight
    if _beside_the_stack(config)[1]:
        # a second prediction head (`mtp_hidden`): the last layer's output merged
        # with the next token's embedding, one more block, the SAME head on the
        # targets shifted by one; the last position has no target there
        targets, mask = batch["targets"], batch.get("mask")
        m, mtp_stats = mtp_hidden(params, h_last, targets, config, block)
        with jax.named_scope("mtp.head"):
            m = rms_norm(m, params["mtp"]["final_norm"], config.rms_eps)
            ahead = jnp.pad(targets[:, 1:], ((0, 0), (0, 1)))
            has_target = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
            if mask is not None:
                has_target = has_target & (mask * jnp.pad(mask[:, 1:], ((0, 0), (0, 1))) > 0)
            loss_mtp, _ = fused_cross_entropy_loss(
                m, output_weight(params), ahead, jnp.broadcast_to(has_target, targets.shape))
        stats = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]), stats, mtp_stats)
        stats.update(loss_main=loss, loss_mtp=loss_mtp)
        loss = loss + config.mtp_loss_weight * loss_mtp
    router = (config.router_aux_coeff * stats["balance_loss"].mean()
              + config.router_z_coeff * stats["z_loss"].mean())
    return loss + router, weight, stats
