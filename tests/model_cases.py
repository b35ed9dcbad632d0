"""What the model files (tests/test_zaya.py, test_glm_lite.py,
test_laguna.py (Laguna, Mellum2 and SDAR: one stack), test_keye.py,
test_olmo_hybrid.py, test_nemotron_h.py, test_solar_open2.py, test_kimi_linear.py, test_moe.py),
the files of their
train paths (tests/test_contract_<model>.py) and
tests/test_model_contract.py share. No test lives here (pytest does not
collect the file).

A model is a row of `MODELS`: its tiny preset in float32, its plain
reference, its `shape_of` (the reference reads the configuration file's
key names) and the names of the leaves that `init_params` leaves at one
or zero. Everything else is written once: the tokens, the seeded
parameters, the worst leaf of two gradient trees, the train path and the
reference's taken ONCE a process for one configuration, under `jax.jit`
(`train_path`, `reference_path`: a whole-model gradient taken bare is
traced operation by operation, four to five times the seconds), and the
contract's two cases that compile that path (`contract_cases`). Under
`--dist loadfile` a process is a FILE, so whatever reads a
configuration's path stands in one file, tests/test_contract_<model>.py:
the model's train path against its reference, what else reads its
statistics or gradients, and the row's remat and bf16 cases. The next
model costs a row, a `shape_of` and such a file."""

import contextlib
import dataclasses
import functools
import json
import os
import types
from typing import Callable
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import (glm_lite_decoder, keye_decoder, kimi_linear_decoder,
                                 laguna_decoder, mellum2_decoder, nemotron_h_decoder,
                                 olmo_hybrid_decoder, sdar_decoder, solar_open2_decoder,
                                 zaya_decoder)
from ray_tpu.models import (block_diffusion, cca, dsa, kimi_linear, laguna, llama, mla, nemotron_h,
                            olmo_hybrid, solar_open2)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# -- the batch, the parameters, the worst leaf ----------------------------------------


def skewed_tokens(cfg, batch, seq, seed=1, power=1.1) -> dict:
    """Zipf-like tokens: a few ids make most of the batch, as the
    benchmark's traffic does, so the experts' groups are uneven."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, cfg.vocab_size + 1) ** power
    ids = rng.choice(cfg.vocab_size, size=(batch, seq + 1), p=p / p.sum())
    return {"tokens": jnp.asarray(ids[:, :-1], jnp.int32),
            "targets": jnp.asarray(ids[:, 1:], jnp.int32)}


def uniform_tokens(cfg, batch, seq, seed=1) -> dict:
    tok = jax.random.randint(jax.random.key(seed), (batch, seq + 1), 0, cfg.vocab_size)
    return {"tokens": tok[:, :-1], "targets": tok[:, 1:]}


def spread(tree: dict, scales: dict, keys) -> None:
    """Move the named leaves off the one or zero they start at, each by its
    scale x a normal table from the next key, so that a test sees them."""
    for name, scale in scales.items():
        tree[name] = tree[name] + scale * jax.random.normal(next(keys), tree[name].shape)


def worst_leaf(got, want, skip=("router_bias",)) -> dict:
    """{path: largest difference of a leaf over the leaf's own scale}; a
    leaf named in `skip` takes no gradient on either side."""
    worst = {}
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = want
        for k in path:
            w = w[k.key]
        name = jax.tree_util.keystr(path)
        # on the host: jax.numpy taken bare compiles each operation for each leaf's shape
        g, w = np.asarray(g), np.asarray(w)
        if any(s in name for s in skip):
            assert not g.any() and not w.any()
            continue
        worst[name] = float(np.abs(g - w).max()) / max(float(np.abs(w).max()), 1e-12)
    return worst


# -- a model's row -----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class Model:
    name: str                      # the id of its cases
    fp32: object                   # the tiny preset, computed in float32
    batch: int
    seq: int
    reference: types.ModuleType    # chipbench/reference/<...>_decoder.py
    shape_of: Callable             # a configuration as the reference reads it
    norms: Callable                # params -> [(subtree, {leaf: scale})], in the keys' order
    n_keys: int                    # how many keys the seed is split into
    bias: float                    # the selection biases' scale where a test names none
    preset: str                    # the registry's name of the published configuration
    tiny: str                      # the registry's name of the tiny preset
    refused_as: str                # what the engine's refusal names
    catalog: str                   # the catalog's name of the configuration
    config_file: str               # chipbench/configs/<...>, where the catalog is absent
    facts: dict                    # {field: value} of the published preset
    # what the contract's cases differ by from model to model: every row states each
    remat_plain: dict              # the remat cases' plain configuration, over fp32
    remat_bias: float              # the selection biases' scale under the remat cases
    remat_tol: dict                # the remat gradients against the plain ones
    bf16: dict                     # the bf16 case's configuration, over fp32
    bf16_rel: float                # its loss against the reference's
    tokens: Callable               # (cfg, batch, seq) -> the batch
    reference_set_up: Callable     # a context around the reference's calls

    def batch_of(self, cfg) -> dict:
        return self.tokens(cfg, self.batch, self.seq)


@functools.lru_cache(maxsize=None)
def _seeded(model: Model, cfg, bias, seed):
    # taken bare on purpose: the tree is made operation by operation, each compiled once a
    # shape and PROCESS, so a file's second configuration costs 0.2 s where the first cost
    # 5-28; under `jax.jit` every configuration is a program of its own (13 s for GLM's)
    params = llama.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), model.n_keys))
    for tree, scales in model.norms(params):
        spread(tree, scales, keys)
    if "router_bias" in params["layers"]:  # an expert configuration's
        table = params["layers"]["router_bias"]
        params["layers"]["router_bias"] = bias * jax.random.normal(next(keys), table.shape)
    return params


def seeded_params(model: Model, cfg, bias=None, seed=0):
    """init_params with the leaves that start at one or zero moved off
    them (a norm's scale at one would hide the norm) and the selection
    biases at `bias` x a random table (0: the published forward). Made
    once a configuration; the caller's copy of the tree is its own."""
    params = _seeded(model, cfg, model.bias if bias is None else bias, seed)
    return jax.tree.map(lambda x: x, params)


def _once_a_configuration(made):
    """`made(model, cfg, bias)` once a process for one configuration and
    scale of the selection biases (None: the model's own)."""
    cached = functools.lru_cache(maxsize=None)(made)
    return functools.wraps(made)(lambda model, cfg, bias=None: cached(
        model, cfg, model.bias if bias is None else bias))


@_once_a_configuration
def train_path(model: Model, cfg, bias) -> types.SimpleNamespace:
    """llama.loss_and_weight_fn (the one train path) on the model's seeded
    parameters and batch: loss, weight, stats and every gradient, from
    one jitted program at the matmuls' highest precision. Once a
    configuration and bias a PROCESS: its callers (a model's train-path
    case, whatever reads that path's statistics, the remat cases' plain
    side) stand in one file, tests/test_contract_<model>.py, because a
    second file is a second process and compiles it again."""
    params, batch = seeded_params(model, cfg, bias), model.batch_of(cfg)

    def f(p):
        # a dense configuration hands out no statistics
        loss, weight, *stats = llama.loss_and_weight_fn(p, batch, cfg)
        return loss, (weight, stats[0] if stats else None)

    with jax.default_matmul_precision("highest"):
        (loss, (weight, stats)), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(params)
    return types.SimpleNamespace(params=params, batch=batch, loss=loss, weight=weight,
                                 stats=stats, grads=grads)


@_once_a_configuration
def reference_path(model: Model, cfg, bias) -> types.SimpleNamespace:
    """The plain reference on the same parameters and batch: its
    `loss_parts` and the gradients of its loss, from one jitted program."""
    params, batch, shape = seeded_params(model, cfg, bias), model.batch_of(cfg), model.shape_of(cfg)

    def f(p):
        parts = model.reference.loss_parts(p, batch["tokens"], batch["targets"], shape)
        return parts["loss"], parts

    with model.reference_set_up(), jax.default_matmul_precision("highest"):
        (_, parts), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(params)
    return types.SimpleNamespace(parts=parts, grads=grads)


def contract_cases(*models) -> tuple:
    """(test_remat_gives_the_same_gradients,
    test_bf16_compute_stays_near_the_reference) for these rows: the
    contract's two cases that compile the train path, made here once and
    collected by the file that holds the rows' train-path cases
    (tests/test_contract_<model>.py), so that one process compiles a
    configuration's plain path for both."""
    by_name = pytest.mark.parametrize("model", models, ids=lambda m: m.name)

    @pytest.mark.parametrize("remat_policy", ["dots", "full"])
    @by_name
    def test_remat_gives_the_same_gradients(model, remat_policy):
        """The loss and every gradient of the rematerialised train path are
        the plain one's, on the parameters the model's own file gave this test
        before it was one: the selection biases a random table at the row's
        `remat_bias` (ZAYA1 0.05, GLM-4.7-Flash 0.1, Laguna 0.05 over the
        dense layer and one period, Mellum2 0.05 over one period, Keye 0), at
        the model's own tolerance.
        The plain gradients are made once for both policies, and where the
        configuration and the bias are its train-path case's they are that
        case's too."""
        plain = dataclasses.replace(model.fp32, **model.remat_plain)
        cfg = dataclasses.replace(plain, remat=True, remat_policy=remat_policy)
        want = train_path(model, plain, model.remat_bias)
        got = train_path(model, cfg, model.remat_bias)
        assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-6)
        for g, w in zip(jax.tree.leaves(got.grads), jax.tree.leaves(want.grads)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **model.remat_tol)

    @by_name
    def test_bf16_compute_stays_near_the_reference(model):
        """The loss in bfloat16 (Laguna's, Mellum2's and Keye's through the
        flash kernels, interpreted) against the plain reference's on the same
        bfloat16 parameters."""
        cfg = dataclasses.replace(model.fp32, dtype=jnp.bfloat16, **model.bf16)
        params, batch = seeded_params(model, cfg), model.batch_of(cfg)
        loss = jax.jit(lambda p: llama.loss_fn(p, batch, cfg))(params)
        shape = model.shape_of(cfg)
        with model.reference_set_up():   # one program: bare, each of its operations is compiled alone
            ref = jax.jit(lambda p: model.reference.loss(p, batch["tokens"], batch["targets"],
                                                         shape))(params)
        assert float(loss) == pytest.approx(float(ref), rel=model.bf16_rel)

    return test_remat_gives_the_same_gradients, test_bf16_compute_stays_near_the_reference


def catalog_config(model: Model) -> dict:
    """The published configuration: the catalog's row, or where there is no
    catalog the benchmark's configuration file with its published values
    put back."""
    if os.path.exists(CATALOG):
        for line in open(CATALOG):
            row = json.loads(line)
            if row["name"] == model.catalog:
                return row["config"]
    file = json.load(open(os.path.join(REPO, "chipbench", "configs", model.config_file)))
    return {**{k: v for k, v in file.items() if k not in file["published"]}, **file["published"]}


# -- the rows ------------------------------------------------------------------------------


def zaya_shape(cfg) -> dict:
    """A ZayaConfig as the configuration file's dict (HF key names)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "cca_time0": cfg.conv_kernels[0], "cca_time1": cfg.conv_kernels[1],
        "partial_rotary_factor": cfg.rotary_fraction,
        "rope_parameters": {"hybrid": {"rope_theta": cfg.rope_theta}},
        "rms_norm_eps": cfg.rms_eps, "router_hidden_size": cfg.router_hidden,
        "num_experts": cfg.n_held, "published": {"num_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held},
        "num_experts_per_tok": cfg.top_k, "max_position_embeddings": cfg.max_seq,
        "num_hidden_layers": cfg.n_layers, "tie_word_embeddings": cfg.tie_embeddings,
        "vocab_size": cfg.vocab_size,
    }


def glm_lite_shape(cfg) -> dict:
    """A GlmLiteConfig as the configuration file's dict (HF key names)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_eps, "n_routed_experts": cfg.n_held,
        "published": {"n_routed_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held},
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling,
        "first_k_dense_replace": cfg.first_dense_layers,
        "num_nextn_predict_layers": cfg.mtp_layers, "mtp_loss_weight": cfg.mtp_loss_weight,
        "max_position_embeddings": cfg.max_seq, "num_hidden_layers": cfg.n_layers,
        "tie_word_embeddings": cfg.tie_embeddings, "vocab_size": cfg.vocab_size,
    }


def _rope_group(r: laguna.Rotary) -> dict:
    return {"rope_theta": r.theta, "rope_type": r.rope_type, "factor": r.factor,
            "original_max_position_embeddings": r.original_max, "beta_fast": r.beta_fast,
            "beta_slow": r.beta_slow, "attention_factor": r.attention_factor,
            "partial_rotary_factor": r.partial}


def laguna_shape(cfg) -> dict:
    """A LagunaConfig as the configuration file's dict (HF key names)."""
    n = cfg.n_layers
    return {
        "hidden_size": cfg.d_model, "head_dim": cfg.head_dim,
        "num_key_value_heads": cfg.n_kv_heads, "num_hidden_layers": n,
        "num_attention_heads_per_layer": list(cfg.heads_per_layer[:n]),
        "layer_types": list(cfg.layer_types[:n]), "sliding_window": cfg.sliding_window,
        "mlp_layer_types": ["dense"] * cfg.first_dense_layers + ["sparse"] * cfg.n_expert_layers,
        "rope_parameters": {laguna.FULL: _rope_group(cfg.rope_full),
                            laguna.SLIDING: _rope_group(cfg.rope_sliding)},
        "rms_norm_eps": cfg.rms_eps, "num_experts": cfg.n_held,
        "published": {"num_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held},
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
        "moe_routed_scaling_factor": cfg.routed_scaling,
        "max_position_embeddings": cfg.max_seq, "tie_word_embeddings": cfg.tie_embeddings,
        "vocab_size": cfg.vocab_size,
    }


def mellum2_shape(cfg) -> dict:
    """A LagunaConfig of Mellum2's kind as the configuration file's dict (HF key names)."""
    shape = laguna_shape(cfg)
    del shape["num_attention_heads_per_layer"], shape["moe_routed_scaling_factor"]
    return {**shape, "num_attention_heads": cfg.n_heads}


def sdar_shape(cfg) -> dict:
    """A LagunaConfig of SDAR's kind as the configuration file's dict (HF key names)."""
    return {
        "hidden_size": cfg.d_model, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "num_hidden_layers": cfg.n_layers, "rope_theta": cfg.rope_full.theta,
        "rms_norm_eps": cfg.rms_eps, "num_experts": cfg.n_held,
        "published": {"num_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held},
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
        "max_position_embeddings": cfg.max_seq, "tie_word_embeddings": cfg.tie_embeddings,
        "vocab_size": cfg.vocab_size,
        "block_diffusion": {"block_length": cfg.diffusion_block, "eps": block_diffusion.EPS},
    }


def keye_shape(cfg) -> dict:
    """A KeyeConfig as the configuration file's dict (HF key names)."""
    return {
        "hidden_size": cfg.d_model, "head_dim": cfg.head_dim,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "num_hidden_layers": cfg.n_layers, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_eps, "num_experts": cfg.n_held,
        "sa_config": {"indexer_num_heads": cfg.indexer_heads,
                      "indexer_head_dim": cfg.indexer_head_dim, "topk": cfg.indexer_topk},
        "published": {"num_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held},
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
        "max_position_embeddings": cfg.max_seq, "tie_word_embeddings": cfg.tie_embeddings,
        "mlp_only_layers": [], "decoder_sparse_step": 1, "vocab_size": cfg.vocab_size,
    }


def olmo_hybrid_shape(cfg) -> dict:
    """An OlmoHybridConfig as the configuration file's dict (HF key names)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "num_hidden_layers": cfg.n_layers,
        "intermediate_size": cfg.d_ff, "layer_types": list(cfg.layer_types[:cfg.n_layers]),
        "linear_num_key_heads": cfg.linear_heads, "linear_num_value_heads": cfg.linear_heads,
        "linear_key_head_dim": cfg.linear_key_dim, "linear_value_head_dim": cfg.linear_value_dim,
        "linear_conv_kernel_dim": cfg.conv_kernel,
        "linear_allow_neg_eigval": cfg.allow_neg_eigval, "rms_norm_eps": cfg.rms_eps,
        "rope_parameters": {"rope_theta": None}, "max_position_embeddings": cfg.max_seq,
        "tie_word_embeddings": cfg.tie_embeddings, "vocab_size": cfg.vocab_size,
    }


def nemotron_h_shape(cfg) -> dict:
    """A NemotronHConfig as the configuration file's dict (HF key names)."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.n_layers, "hybrid_override_pattern": cfg.pattern,
        "mamba_num_heads": cfg.mamba_heads, "mamba_head_dim": cfg.mamba_head_dim,
        "n_groups": cfg.ssm_groups, "ssm_state_size": cfg.ssm_state,
        "conv_kernel": cfg.conv_kernel, "chunk_size": cfg.chunk_size, "use_conv_bias": True,
        "mamba_proj_bias": False, "layer_norm_epsilon": cfg.rms_eps,
        "moe_intermediate_size": cfg.d_ff, "moe_shared_expert_intermediate_size": cfg.shared_d_ff,
        "n_routed_experts": cfg.n_held, "published": {"n_routed_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held}, "n_shared_experts": 1,
        "n_group": 1, "topk_group": 1, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scaling,
        "max_position_embeddings": cfg.max_seq, "tie_word_embeddings": cfg.tie_embeddings,
        "vocab_size": cfg.vocab_size,
    }


def solar_open2_shape(cfg) -> dict:
    """A SolarOpen2Config as the configuration file's dict (HF key names): the
    head counts and experts HELD under their published keys."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.n_layers, "gqa_layers": list(cfg.gqa_layers),
        "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
        "linear_attn_config": {"short_conv_kernel_size": cfg.conv_kernel,
                               "head_dim": cfg.kda_head_dim, "num_heads": cfg.kda_heads,
                               "num_kv_heads": None},
        "moe_intermediate_size": cfg.d_ff, "rms_norm_eps": cfg.rms_eps,
        "n_routed_experts": cfg.n_held, "published": {"n_routed_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held}, "n_shared_experts": 1,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling, "max_position_embeddings": cfg.max_seq,
        "tie_word_embeddings": cfg.tie_embeddings, "vocab_size": cfg.vocab_size,
    }


def kimi_linear_shape(cfg) -> dict:
    """A KimiLinearConfig as the configuration file's dict (HF key names): the
    experts HELD under their published key, the layers numbered from 1."""
    every = max(cfg.n_layers, max(cfg.mla_layers))
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "num_hidden_layers": cfg.n_layers,
        "intermediate_size": cfg.dense_d_ff, "first_k_dense_replace": cfg.first_dense_layers,
        "q_lora_rank": None, "kv_lora_rank": cfg.kv_lora_rank, "mla_use_nope": True,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "linear_attn_config": {
            "short_conv_kernel_size": cfg.conv_kernel, "head_dim": cfg.kda_head_dim,
            "num_heads": cfg.kda_heads, "full_attn_layers": list(cfg.mla_layers),
            "kda_layers": [l for l in range(1, every + 1) if l not in cfg.mla_layers]},
        "moe_intermediate_size": cfg.d_ff, "rms_norm_eps": cfg.rms_eps, "moe_layer_freq": 1,
        "num_experts": cfg.n_held, "published": {"num_experts": cfg.n_experts},
        "deployment": {"first_expert_held": cfg.first_expert_held}, "num_shared_experts": 1,
        "num_expert_group": 1, "topk_group": 1, "num_nextn_predict_layers": 0,
        "num_experts_per_token": cfg.top_k, "moe_renormalize": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling, "model_max_length": cfg.max_seq,
        "tie_word_embeddings": cfg.tie_embeddings, "vocab_size": cfg.vocab_size,
    }


_LN = {"ln1": 0.2, "ln2": 0.2}
_MLA_NORMS = {**_LN, "q_a_norm": 0.2, "kv_a_norm": 0.2}
_REMAT_TOL = dict(rtol=1e-4, atol=2e-6)


def _typed_blocks(params) -> list:
    return [block for group in ("period", "tail")
            for block in params["layers"].get(group, {}).values()]


def _laguna_norms(params) -> list:
    return [(params["dense_layers"], _LN), *((block, _LN) for block in _typed_blocks(params)),
            (params, {"final_norm": 0.2})]


def _mellum2_norms(params) -> list:
    normed = {**_LN, "q_norm": 0.2, "k_norm": 0.2}
    return [*((block, normed) for block in _typed_blocks(params)), (params, {"final_norm": 0.2})]


ZAYA = Model(
    name="zaya", fp32=dataclasses.replace(cca.ZAYA_TINY, dtype=jnp.float32), batch=2, seq=24,
    reference=zaya_decoder, shape_of=zaya_shape, n_keys=8, bias=0.05,
    # the temperature, the router's carried scale and its norm beside the block's norms
    norms=lambda p: [(p["layers"], {"temp": 0.3, "router_gamma": 0.5, "router_norm": 0.3, **_LN})],
    preset="zaya1-8b", tiny="zaya-tiny", refused_as="ZAYA1", catalog="ZAYA1-8B",
    config_file="zaya1-8b-train.json", facts={"head_dim": 128},
    remat_plain={}, remat_bias=0.05, remat_tol=_REMAT_TOL, bf16={}, bf16_rel=0.02,
    tokens=skewed_tokens, reference_set_up=contextlib.nullcontext,
)
GLM_LITE = Model(
    name="glm_lite", fp32=dataclasses.replace(mla.GLM_LITE_TINY, dtype=jnp.float32),
    batch=2, seq=24, reference=glm_lite_decoder, shape_of=glm_lite_shape, n_keys=64, bias=0.1,
    norms=lambda p: [(p["layers"], _MLA_NORMS), (p["dense_layers"], _MLA_NORMS),
                     (p["mtp"]["block"], _MLA_NORMS),
                     (p["mtp"], {"enorm": 0.2, "hnorm": 0.2, "final_norm": 0.2}),
                     (p, {"final_norm": 0.2})],
    preset="glm-4.7-flash", tiny="glm-lite-tiny", refused_as="GLM-4.7-Flash",
    catalog="GLM-4.7-Flash", config_file="glm-4.7-flash-train.json",
    facts={"head_dim": 256, "n_expert_layers": 46, "first_dense_layers": 1, "mtp_layers": 1,
           "router_score": "sigmoid", "routed_scaling": 1.8, "shared_d_ff": 1536},
    remat_plain={}, remat_bias=0.1, remat_tol=_REMAT_TOL, bf16={}, bf16_rel=0.02,
    tokens=skewed_tokens, reference_set_up=contextlib.nullcontext,
)
LAGUNA = Model(
    name="laguna", fp32=dataclasses.replace(laguna.LAGUNA_TINY, dtype=jnp.float32),
    batch=2, seq=40,   # the window (24) shorter than the sequence
    reference=laguna_decoder, shape_of=laguna_shape, n_keys=64, bias=0.0, norms=_laguna_norms,
    preset="laguna-s-2.1", tiny="laguna-tiny", refused_as="Laguna", catalog="Laguna-S-2.1",
    config_file="laguna-s-2.1-train.json",
    facts={"head_dim": 128, "rope_full.attention_factor": 1.4852030263919618,
           "rope_full.partial": 0.5},
    remat_plain=dict(n_layers=5), remat_bias=0.05,   # the dense layer and one period
    remat_tol=_REMAT_TOL, bf16=dict(attention_impl="flash", n_layers=5), bf16_rel=0.02,
    tokens=skewed_tokens, reference_set_up=contextlib.nullcontext,
)
MELLUM2 = Model(
    name="mellum2", fp32=dataclasses.replace(laguna.MELLUM2_TINY, dtype=jnp.float32),
    batch=2, seq=64,   # the window (24) shorter than the sequence, four times yarn's original 16
    reference=mellum2_decoder, shape_of=mellum2_shape, n_keys=64, bias=0.0, norms=_mellum2_norms,
    preset="mellum2-12b-a2.5b", tiny="mellum2-tiny", refused_as="Mellum2",
    catalog="Mellum2-12B-A2.5B-Instruct", config_file="mellum2-12b-a2.5b-train.json",
    facts={"head_dim": 128, "rope_full.attention_factor": 1.2772588722239782,
           "rope_full.partial": 1.0, "rope_full.factor": 16.0, "rope_sliding.theta": 500000.0,
           "attn_gate": "none", "qk_head_norm": True, "heads_per_layer": (),
           "first_dense_layers": 0, "shared_d_ff": 0, "routed_scaling": 1.0, "n_experts": 64,
           "top_k": 8, "d_ff": 896, "sliding_window": 1024, "n_kv_heads": 4},
    remat_plain=dict(n_layers=4), remat_bias=0.05,   # one period
    remat_tol=_REMAT_TOL, bf16=dict(attention_impl="flash", n_layers=4), bf16_rel=0.02,
    tokens=skewed_tokens,
    # the reference walks its queries in blocks: four of them at this size
    reference_set_up=lambda: mock.patch.object(mellum2_decoder, "QUERY_BLOCK", 16),
)
SDAR = Model(
    name="sdar", fp32=dataclasses.replace(laguna.SDAR_TINY, dtype=jnp.float32),
    batch=2, seq=40,   # ten blocks of 4; two copies: 80 rows, no multiple of a tile of 32
    reference=sdar_decoder, shape_of=sdar_shape, n_keys=64, bias=0.0, norms=_mellum2_norms,
    preset="sdar-30b-a3b", tiny="sdar-tiny", refused_as="SDAR", catalog="SDAR-30B-A3B-Chat",
    config_file="sdar-30b-a3b-train.json",
    facts={"head_dim": 128, "diffusion_block": 4,
           "layer_types": ("full_attention",) * 48, "rope_full.theta": 1000000.0,
           "rope_full.rope_type": "default", "rope_full.partial": 1.0, "attn_gate": "none",
           "qk_head_norm": True, "heads_per_layer": (), "first_dense_layers": 0, "shared_d_ff": 0,
           "routed_scaling": 1.0, "n_experts": 128, "top_k": 8, "d_ff": 768, "n_kv_heads": 4,
           "max_seq": 32768},
    remat_plain={}, remat_bias=0.05, remat_tol=_REMAT_TOL,
    bf16=dict(attention_impl="flash"), bf16_rel=0.02, tokens=skewed_tokens,
    # the reference walks its queries in blocks: five of them at this size
    reference_set_up=lambda: mock.patch.object(sdar_decoder, "QUERY_BLOCK", 16),
)
KEYE = Model(
    name="keye", fp32=dataclasses.replace(dsa.KEYE_TINY, dtype=jnp.float32),
    batch=2, seq=64,   # topk 16 and chunks of 16 queries: the first chunk computes no score
    reference=keye_decoder, shape_of=keye_shape, n_keys=8, bias=0.0,
    norms=lambda p: [(p["layers"], {"ln1": 0.1, "ln2": 0.1, "q_norm": 0.1, "k_norm": 0.1,
                                    "idx_norm_w": 0.1, "idx_norm_b": 0.1})],
    preset="keye-vl-2.0-30b-a3b", tiny="keye-tiny", refused_as="Keye",
    catalog="Keye-VL-2.0-30B-A3B", config_file="keye-vl-2.0-30b-a3b-train.json",
    facts={"head_dim": 128, "indexer_heads": 16, "indexer_head_dim": 64, "indexer_topk": 2048,
           "index_chunk": 512, "n_experts": 128, "top_k": 8, "norm_topk_prob": True,
           "selection_bias": True, "shared_d_ff": 0},
    remat_plain={}, remat_bias=0.0, remat_tol=dict(rtol=1e-5, atol=1e-7),
    bf16=dict(attention_impl="flash"), bf16_rel=5e-3, tokens=uniform_tokens,
    # the reference walks its queries in blocks: two of them at this size
    reference_set_up=lambda: mock.patch.object(keye_decoder, "QUERY_BLOCK", 32),
)


def _olmo_hybrid_norms(params) -> list:
    period = params["layers"]["period"]
    linear = {**_LN, "o_norm": 0.2, "A_log": 0.3, "dt_bias": 0.3}
    return [*((period[j], linear) for j in "012"),
            (period["3"], {**_LN, "q_norm": 0.2, "k_norm": 0.2}), (params, {"final_norm": 0.2})]


OLMO_HYBRID = Model(
    name="olmo_hybrid",
    fp32=dataclasses.replace(olmo_hybrid.OLMO_HYBRID_TINY, dtype=jnp.float32),
    batch=2, seq=150,   # two chunks of 64 and 22 positions more: no multiple of the chunk
    reference=olmo_hybrid_decoder, shape_of=olmo_hybrid_shape, n_keys=32, bias=0.0,
    norms=_olmo_hybrid_norms, preset="olmo-hybrid-7b", tiny="olmo-hybrid-tiny",
    refused_as="Olmo-Hybrid", catalog="Olmo-Hybrid-7B", config_file="olmo-hybrid-7b-train.json",
    # its heads ARE d_model / heads wide: the row states no `head_dim`
    facts={"linear_heads": 30, "linear_key_dim": 96, "linear_value_dim": 192, "conv_kernel": 4,
           "allow_neg_eigval": True, "n_kv_heads": 30, "d_ff": 11008, "rope_theta": 0.0},
    # ONE period: a norm on a sublayer's OUTPUT divides the Jacobian by that output's size, and
    # a fresh full-attention layer's output (an average of random values) is small, so float32's
    # own rounding, which remat reorders, grows about a hundredfold a period (3e-6 after one
    # block, 1e-4 after four, 1e-2 after eight; the same on the reference's side).
    # THE LIMIT bounds a DRAW of XLA:CPU's rounding, not a property of the rule (PR 47's
    # readings, PERF.md section 6; shares of PR 46's rtol 2e-3 / atol 2e-5, worst element):
    # - over parameter seeds 0-23 on the CPU, `dots` (`full` agrees to two digits): the
    #   jax.numpy scan of PR 46 0.04-0.88, median 0.26, this seed 0.37; the kernels 0.03-2.66,
    #   median 0.22, this seed 1.04 (kernels / scan seed by seed 0.5-4.2: no factor);
    # - ON THE CHIP (the kernels through Mosaic) 0.0000-0.0001 on eight seeds, both policies:
    #   there the rematerialised gradient is the plain one; the scatter is XLA:CPU's, which
    #   compiles the two programs' neighbours of the rule differently;
    # - against the reference in float64, leaf by leaf: either program is as far from it as
    #   from its rematerialised twin (seed 0, period 0's wv: kernels 4.6e-4 plain / 2.2e-4
    #   remat, scan 3.6e-4 / 2.75e-4; seed 11, period 2's wk: kernels 1.3e-3 / 6.0e-4, scan
    #   4.3e-3 / 4.1e-3: the scan's two are nearer each other and FARTHER from float64);
    # - the difference enters at layer 2's wq, wk, conv_q, conv_k, wb (what the rule's dq, dk,
    #   dbeta feed: 50-150 x the layer's other leaves, in both forms); the rule alone on those
    #   inputs is 1-3e-6 from float64 in both forms, moves by 2-5e-7 when its inputs move by
    #   float32's 6e-8 (it amplifies nothing), and rematerialised equals itself bit for bit;
    #   with its inputs and outputs pinned by ordered callbacks both forms read the same to
    #   two digits in every column.
    # So 2e-3 was 2.7 x over ONE draw of a quantity that spreads thirtyfold over seeds; 5e-3 /
    # 5e-5 gives this seed's draw the same room (0.42). A wrong gradient reads hundreds. A
    # draw over 1 after a later change is told from a fault by `dots` = `full`, by the float64
    # distances above and by the chip's reading
    remat_plain=dict(n_layers=4), remat_bias=0.0, remat_tol=dict(rtol=5e-3, atol=5e-5),
    bf16=dict(attention_impl="flash"), bf16_rel=0.02,
    tokens=skewed_tokens, reference_set_up=contextlib.nullcontext,
)
def _nemotron_h_norms(params) -> list:
    layers = params["layers"]
    return [(layers["mamba"], {"ln": 0.2, "norm": 0.2, "A_log": 0.3, "dt_bias": 0.3, "D": 0.3}),
            (layers["attention"], {"ln": 0.2}), (layers["experts"], {"ln": 0.2}),
            (params, {"final_norm": 0.2})]


NEMOTRON_H = Model(
    name="nemotron_h",
    fp32=dataclasses.replace(nemotron_h.NEMOTRON_H_TINY, dtype=jnp.float32),
    batch=2, seq=40,   # two chunks of 16 and 8 positions more: no multiple of the chunk
    reference=nemotron_h_decoder, shape_of=nemotron_h_shape, n_keys=16, bias=0.05,
    norms=_nemotron_h_norms, preset="nemotron-twotower-30b-a3b", tiny="nemotron-h-tiny",
    refused_as="Nemotron-H", catalog="Nemotron-Labs-TwoTower-30B-A3B-Base-BF16",
    config_file="nemotron-twotower-30b-a3b-train.json",
    facts={"head_dim": 128, "mamba_heads": 64, "mamba_head_dim": 64, "ssm_groups": 8,
           "ssm_state": 128, "conv_kernel": 4, "chunk_size": 128, "n_experts": 128, "top_k": 6,
           "router_score": "sigmoid", "routed_scaling": 2.5, "shared_d_ff": 3712, "d_ff": 1856,
           "expert_act": "relu2", "n_kv_heads": 2, "rope_theta": 0.0, "published_layers": 52,
           "pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"},
    remat_plain={}, remat_bias=0.05, remat_tol=_REMAT_TOL,
    bf16=dict(attention_impl="flash"), bf16_rel=0.02,
    tokens=skewed_tokens, reference_set_up=contextlib.nullcontext,
)


def _solar_open2_norms(params) -> list:
    period = params["layers"]["period"]
    kda = {**_LN, "o_norm": 0.2, "A_log": 0.3, "dt_bias": 0.3, "g_bias": 0.3}
    return [(period["0"], _LN), *((period[j], kda) for j in "123"), (params, {"final_norm": 0.2})]


SOLAR_OPEN2 = Model(
    name="solar_open2",
    fp32=dataclasses.replace(solar_open2.SOLAR_OPEN2_TINY, dtype=jnp.float32),
    batch=2, seq=150,   # two chunks of 64 and 22 positions more: no multiple of the chunk
    reference=solar_open2_decoder, shape_of=solar_open2_shape, n_keys=32, bias=0.05,
    norms=_solar_open2_norms, preset="solar-open2-250b", tiny="solar-open2-tiny",
    refused_as="Solar-Open2", catalog="Solar-Open2-250B",
    config_file="solar-open2-250b-train.json",
    facts={"head_dim": 128, "kda_heads": 64, "kda_head_dim": 128, "kda_rank": 128,
           "conv_kernel": 4, "n_heads": 64,
           "n_kv_heads": 8, "n_experts": 320, "top_k": 8, "router_score": "sigmoid",
           "routed_scaling": 1.0, "shared_d_ff": 1280, "d_ff": 1280, "rope_theta": 0.0,
           "gqa_layers": tuple(range(0, 48, 4))},
    remat_plain={}, remat_bias=0.05, remat_tol=_REMAT_TOL,
    bf16=dict(attention_impl="flash"), bf16_rel=0.02,
    tokens=skewed_tokens, reference_set_up=contextlib.nullcontext,
)


def _kimi_linear_norms(params) -> list:
    kda = {**_LN, "o_norm": 0.2, "A_log": 0.3, "dt_bias": 0.3, "g_bias": 0.3}
    blocks = [params["dense_layers"], *_typed_blocks(params)]
    return [*((b, kda if "wb" in b else {**_LN, "kv_a_norm": 0.2}) for b in blocks),
            (params, {"final_norm": 0.2})]


KIMI_LINEAR = Model(
    name="kimi_linear",
    fp32=dataclasses.replace(kimi_linear.KIMI_LINEAR_TINY, dtype=jnp.float32),
    batch=2, seq=150,   # two chunks of 64 and 22 positions more: no multiple of the chunk
    reference=kimi_linear_decoder, shape_of=kimi_linear_shape, n_keys=32, bias=0.05,
    norms=_kimi_linear_norms, preset="kimi-linear-48b-a3b", tiny="kimi-linear-tiny",
    refused_as="Kimi-Linear", catalog="Kimi-Linear-48B-A3B-Instruct",
    config_file="kimi-linear-48b-a3b-train.json",
    facts={"head_dim": 192, "kda_heads": 32, "kda_head_dim": 128, "kda_rank": 128,
           "conv_kernel": 4, "kda_neg_eigval": False, "n_heads": 32, "n_kv_heads": 32,
           "q_lora_rank": 0, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "v_head_dim": 128, "mla_rope": False, "n_experts": 256,
           "top_k": 8, "router_score": "sigmoid", "routed_scaling": 2.446, "shared_d_ff": 1024,
           "d_ff": 1024, "dense_d_ff": 9216, "first_dense_layers": 1, "max_seq": 1048576,
           "mla_layers": (4, 8, 12, 16, 20, 24, 27)},
    remat_plain={}, remat_bias=0.05, remat_tol=_REMAT_TOL,
    bf16=dict(attention_impl="flash"), bf16_rel=0.02,
    tokens=skewed_tokens, reference_set_up=contextlib.nullcontext,
)
MODELS = (ZAYA, GLM_LITE, LAGUNA, MELLUM2, SDAR, KEYE, OLMO_HYBRID, NEMOTRON_H, SOLAR_OPEN2,
          KIMI_LINEAR)
