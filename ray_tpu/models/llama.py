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

The layout of the attention sublayer (PR 38; CCA since PR 33, MLA since
PR 34). q, k and v are HEAD-MAJOR, [B, heads, S, hd], from where the
projections write them (the weight read as [D, heads, hd],
`"bsd,dnh->bnsh"`) to where `wo` contracts (heads, hd) of what the
kernel gives back (`"bhsk,hkd->bsd"`): on a TPU an array's last two
dimensions are its tile, so the tile is (tokens, a head's channels) and
always full, where [B, S, heads, hd] made 8 key-value heads the rows of
a half-empty bfloat16 tile. The q/k norm and the rotary act on the last
axis and on major ones (`_norm_over_heads`,
nn/layers.py::apply_rope_head_major, whose halves change places on the
MXU so that nothing is cut inside the 128 lanes), the flash kernels,
whose own layout this is, take q, k and v as they are
(ops/attention.attention_head_major), and nn/layers.py::head_major, the
one helper this module and models/cca.py share, pins the tile where the
matmuls write. Under `tp > 1` the rings of parallel/tp_overlap.py hand
back and take [B, S, h] slabs in token order: one `swapaxes` a tensor
after the ring and one before `rs_matmul` stand where the kernel
wrapper's three transposes in and one out stood. models/llama_decode.py
(serving: a cache laid out [.., S, heads, hd]) keeps `apply_rope` and
its own layout.
"""

from __future__ import annotations

import dataclasses
import importlib
from functools import partial
from typing import Any, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ray_tpu.nn.layers import (
    apply_rope_head_major,
    fused_cross_entropy_loss,
    head_major,
    init_dense,
    rms_norm,
    rope_frequencies,
    swiglu,
)
from ray_tpu.ops.attention import attention_head_major
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


def _cca(config: LlamaConfig):
    """models/cca.py when the configuration's attention is compressed
    convolutional attention (a `ZayaConfig`), else None: the seam at
    which the block's attention sublayer is chosen, at trace time."""
    if not hasattr(config, "conv_kernels"):
        return None
    from ray_tpu.models import cca

    return cca


def _mla(config: LlamaConfig):
    """models/mla.py when the configuration's attention is multi-head
    latent attention (a `GlmLiteConfig`), else None. Such a configuration
    may also have leading dense layers before its stack of expert layers
    and a multi-token-prediction block after it: that module builds the
    tree, `_trunk` and `loss_and_weight_fn` run it."""
    if not hasattr(config, "kv_lora_rank"):
        return None
    from ray_tpu.models import mla

    return mla


def _own_stack(config: LlamaConfig):
    """The module that builds the tree and runs the layers of a
    configuration whose layers differ in kind within one stack
    (`layer_types`), else None: models/laguna.py (sliding-window and full
    attention at unlike head counts over an expert layer, a scan over
    whole periods of kinds) or models/olmo_hybrid.py (gated-delta-rule
    linear attention and full attention over a dense SwiGLU). The
    configuration names its module (`stack_module`), which has
    `logical_axes(c)`, `init_params(c, key)` and `trunk(params, tokens, c,
    positions=, segment_ids=)`; the head and the loss stay here."""
    if not hasattr(config, "layer_types"):
        return None
    return importlib.import_module(config.stack_module)


def _dsa(config: LlamaConfig):
    """models/dsa.py when the configuration's attention runs over the keys
    a learned indexer selects (a `KeyeConfig`), else None. No other
    configuration loads that module."""
    if not hasattr(config, "indexer_topk"):
        return None
    from ray_tpu.models import dsa

    return dsa


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


def logical_axes(config: LlamaConfig) -> Params:
    """Pytree (parallel to params) of logical-axis tuples."""
    if _own_stack(config) is not None:
        return _own_stack(config).logical_axes(config)
    mla = _mla(config)
    if mla is not None and mla.has_more_than_the_stack(config):
        return mla.logical_axes(config)
    layer = {
        "ln1": ("layers", "norm"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "ln2": ("layers", "norm"),
    }
    moe, cca, dsa = _moe(config), _cca(config), _dsa(config)
    if cca is not None or mla is not None or dsa is not None:
        layer = {"ln1": layer["ln1"], "ln2": layer["ln2"],
                 **(cca or mla or dsa).attention_axes()}
    if moe is None:
        layer.update(
            w_gate=("layers", "embed", "mlp"),
            w_up=("layers", "embed", "mlp"),
            w_down=("layers", "mlp", "embed"),
        )
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
    c = config
    if _own_stack(c) is not None:
        return _own_stack(c).init_params(c, key)
    mla = _mla(c)
    if mla is not None and mla.has_more_than_the_stack(c):
        return mla.init_params(c, key)
    keys = jax.random.split(key, 8)
    hd = c.head_dim
    L = c.n_layers

    def dense(k, shape):
        # init per-layer with distinct keys folded over the layer axis
        ks = jax.random.split(k, L)
        return jax.vmap(lambda kk: init_dense(kk, shape, c.param_dtype))(ks)

    moe = _moe(c)
    if moe is None:
        ffn = {
            "w_gate": dense(keys[5], (c.d_model, c.d_ff)),
            "w_up": dense(keys[6], (c.d_model, c.d_ff)),
            "w_down": dense(keys[7], (c.d_ff, c.d_model)),
        }
    else:
        ffn = moe.expert_params(c, keys[5])
        if c.qk_norm:
            ffn["q_norm"] = jnp.ones((L, c.n_heads * hd), c.param_dtype)
            ffn["k_norm"] = jnp.ones((L, c.n_kv_heads * hd), c.param_dtype)
    cca, dsa = _cca(c), _dsa(c)
    if cca is not None or mla is not None or dsa is not None:
        attn = (cca or mla or dsa).attention_params(c, keys[1])
    else:
        attn = {
            "wq": dense(keys[1], (c.d_model, c.n_heads * hd)),
            "wk": dense(keys[2], (c.d_model, c.n_kv_heads * hd)),
            "wv": dense(keys[3], (c.d_model, c.n_kv_heads * hd)),
            "wo": dense(keys[4], (c.n_heads * hd, c.d_model)),
        }
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


def _norm_over_heads(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """`rms_norm` over the whole projected width of x [B, heads, S, hd]
    (scale [heads * hd]): the mean runs over the head axis and the
    channels, a major axis and the last one, so the tile stays (S, hd)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=(1, 3), keepdims=True)
    scale = scale.astype(jnp.float32).reshape(x.shape[1], 1, x.shape[3])
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _block(
    carry,  # h [B, S, D]; (h, router state [B, S, R]) for an MLP router
    lp: Params,  # one layer's params (no leading layer dim)
    *,
    config: LlamaConfig,
    cos: Optional[jax.Array],
    sin: Optional[jax.Array],
    positions: jax.Array,
    segment_ids: Optional[jax.Array],
    dense_ffn: bool = False,
) -> tuple[Any, Optional[Params]]:
    """One decoder layer -> (carry, the layer's statistics): the
    attention sublayer of the configuration's kind (full causal GQA with
    rotary, and the q/k RMSNorm when the configuration has it, q, k and
    v head-major from the projections to `wo`: the module's layout
    paragraph; or compressed convolutional attention, models/cca.py; or
    multi-head latent attention, models/mla.py; or attention over the keys a
    learned indexer selects, models/dsa.py, whose two counts join the
    layer's statistics), then the dense SwiGLU or the
    expert layer (models/moe.py, whose statistics come back; None for a
    dense layer) by the configuration's own kind; `dense_ffn`: one of an
    expert configuration's leading dense layers. (A configuration whose
    layers differ in kind within the stack, models/laguna.py or
    models/olmo_hybrid.py, has a block of its own beside this one.)"""
    c = config
    moe, cca, mla, dsa = None if dense_ffn else _moe(c), _cca(c), _mla(c), _dsa(c)
    selected = {}
    carries_router = _carries_router_state(c)
    h, router_state = carry if carries_router else (carry, None)
    B, S, D = h.shape
    hd = c.head_dim
    # Under a mesh with tp > 1 the residual stream h stays sharded over
    # `tp` along the tokens and the four matmul sites gather and scatter
    # it inside themselves (parallel/tp_overlap.py); otherwise, and
    # always in llama_decode.py, the plain einsums below. (The rings are
    # the full attention's and the dense MLP's: CCA's and the expert
    # layer's matmuls are the partitioner's to place.)
    mesh = current_mesh()
    overlap = (cca is None and mla is None and dsa is None and mesh is not None
               and mesh.shape.get("tp", 1) > 1)
    if overlap:
        from ray_tpu.parallel.tp_overlap import ag_matmul, rs_matmul

    with jax.named_scope("block.norm"):
        x = rms_norm(h, lp["ln1"], c.rms_eps)
    if cca is not None:
        h = h + cca.cca_sublayer(x, lp, c, positions=positions, segment_ids=segment_ids)
    elif mla is not None:
        h = h + mla.mla_sublayer(x, lp, c, positions=positions, segment_ids=segment_ids)
    elif dsa is not None:
        y, selected = dsa.dsa_sublayer(x, lp, c, positions=positions, segment_ids=segment_ids)
        h = h + y
    else:
        H, dt = c.n_heads, x.dtype
        with jax.named_scope("attn.qkv"):
            ws = [lp[n].astype(dt) for n in ("wq", "wk", "wv")]
            if overlap:
                # the ring hands back [B, S, h] slabs in token order: one swapaxes each
                # (and no pin: under a mesh the layout is the compiler's, `head_major`)
                q, k, v = (jnp.swapaxes(t.reshape(B, S, -1, hd), 1, 2) for t in ag_matmul(x, ws))
            else:
                q, k, v = (head_major(jnp.einsum("bsd,dnh->bnsh", x, w.reshape(D, -1, hd)))
                           for w in ws)
        with jax.named_scope("attn.rope"):
            if moe is not None and c.qk_norm:  # over the whole projected width, before rotary
                q = _norm_over_heads(q, lp["q_norm"], c.rms_eps)
                k = _norm_over_heads(k, lp["k_norm"], c.rms_eps)
            q = apply_rope_head_major(q, cos, sin, positions)
            k = apply_rope_head_major(k, cos, sin, positions)
        with jax.named_scope("attn.attend"):
            o = attention_head_major(q, k, v, causal=True, segment_ids=segment_ids,
                                     impl=c.attention_impl)
            # named so the "dots" remat policy can SAVE it: the policy recognizes
            # dot_general outputs but not a pallas_call's, so without the name the
            # backward pass re-runs the whole flash kernel forward (~25% of a
            # train step) just to rebuild this tensor
            o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        with jax.named_scope("attn.out"):
            wo = lp["wo"].astype(dt)
            if overlap:
                h = h + rs_matmul(jnp.swapaxes(o, 1, 2).reshape(B, S, H * hd), wo)
            else:
                h = h + jnp.einsum("bhsk,hkd->bsd", o, wo.reshape(H, hd, D))

    with jax.named_scope("block.norm"):
        x = rms_norm(h, lp["ln2"], c.rms_eps)
    if moe is not None:
        # the rings of tp_overlap.py are the dense MLP's: under tp > 1
        # the expert layer's matmuls are the partitioner's to place
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
    h, _ = _decoder(params, tokens, config, positions=positions, segment_ids=segment_ids)
    return h


def _remat(block, c: LlamaConfig):
    """`block` rematerialised by the configuration's policy."""
    if not c.remat:
        return block
    if c.remat_policy == "dots":
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                # tp_rs_out: a row-parallel matmul's output where
                # parallel/tp_overlap.py sums it (there the dot the
                # first policy sees is only one chip's product)
                # moe_gate, moe_up: the expert layer's first two
                # grouped matmuls (models/moe.py), which are no
                # dot_general either
                jax.checkpoint_policies.save_only_these_names(
                    "attn_out", "attn_lse", "tp_rs_out", "moe_gate", "moe_up",
                    # dsa_sel: the packed selection of models/dsa.py, which the
                    # backward's kernels read and nothing should compute twice
                    "dsa_sel",
                    # gdn_out, gdn_states: what ops/gated_delta.py's forward kernel
                    # writes, o, the chunks' starting states and their inverses, which
                    # is all its backward kernel reads beside the inputs: the rule runs
                    # twice a layer (forward, backward), not three times
                    "gdn_out", "gdn_states",
                    # ssd_out, ssd_states: likewise ops/ssd.py's forward kernel's y and
                    # the chunks' starting states
                    "ssd_out", "ssd_states",
                ),
            ),
        )
    if c.remat_policy == "full":
        return jax.checkpoint(block)
    raise ValueError(f"unknown remat_policy {c.remat_policy!r}; 'full' or 'dots'")


def _decoder(
    params: Params,
    tokens: jax.Array,
    config: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[Params]]:
    """`hidden_states` and the layers' statistics, each leaf stacked
    over the layers (None for a dense configuration)."""
    h, stats, _ = _trunk(params, tokens, config, positions=positions, segment_ids=segment_ids)
    return rms_norm(h, params["final_norm"], config.rms_eps), stats


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
    mla = _mla(c)
    cos = sin = None
    # CCA rotates part of a head, MLA its decoupled part and DSA two head sizes, from
    # the positions themselves
    if _cca(c) is None and mla is None and _dsa(c) is None:
        with jax.named_scope("attn.rope"):
            cos, sin = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)

    with jax.named_scope("embed"):
        h = params["embed"].astype(c.dtype)[tokens]  # [B, S, D]

    block = partial(
        _block, config=c, cos=cos, sin=sin, positions=positions, segment_ids=segment_ids
    )
    # the stack: under this name stand the scan's own slices and stacked writes and
    # the residual adds that no sublayer's scope holds; every scope inside it wins
    with jax.named_scope("block.stack"):
        layers = params["layers"]
        if mla is not None and mla.has_more_than_the_stack(c):
            # two kinds of block in one model: the leading dense layers run
            # before the scan over the expert layers' stack
            layers = mla.stack_of(params, c)
            dense = _remat(partial(block, dense_ffn=True), c)
            for i in range(c.first_dense_layers):
                h, _ = dense(h, mla.dense_layer(params, i))
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


def output_weight(params: Params) -> jax.Array:
    """[D, V] lm-head weight (tied embedding transpose when untied absent)."""
    w_out = params.get("lm_head", None)
    if w_out is None:
        w_out = params["embed"].T
    return w_out


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
    return jnp.einsum("bsd,dv->bsv", h, w_out.astype(config.dtype))


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
    configuration with a multi-token-prediction block (models/mla.py)
    adds that head's loss at its weight; the statistics then carry the
    block's row after the layers' and the two losses apart
    (`loss_main`, `loss_mtp`).

    Uses the fused lm-head + CE (nn/layers.py fused_cross_entropy_loss):
    the [T, V] fp32 logits/softmax pipeline was ~36% of the flagship
    train step before fusion (round-5 profile).

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
        h = rms_norm(h_last, params["final_norm"], config.rms_eps)
        loss, weight = fused_cross_entropy_loss(
            h, output_weight(params), batch["targets"], batch.get("mask")
        )
    if stats is None:
        return loss, weight
    mla = _mla(config)
    if mla is not None and config.mtp_layers:
        # a second prediction head (models/mla.py): the last layer's output merged
        # with the next token's embedding, one more block, the SAME head on the
        # targets shifted by one; the last position has no target there
        targets, mask = batch["targets"], batch.get("mask")
        m, mtp_stats = mla.mtp_hidden(params, h_last, targets, config, block)
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
