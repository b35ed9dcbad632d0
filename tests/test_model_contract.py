"""What every model that goes through the one decoder holds and that
compiles nothing, a case a model: a row of tests/model_cases.py::MODELS.
`config_from_hf` maps the catalog's config onto the preset and refuses by
name what is not implemented, and the engine refuses the model by name.
The contract's two cases that compile the train path (rematerialisation
gives the gradients it is not there to change, bf16 compute stays near
the plain reference) are `model_cases.contract_cases`, and stand a file a
model beside that model's train path against its reference
(tests/test_contract_<model>.py: one process compiles a configuration
once, and a file is about 150 s alone at most; ROADMAP D8). What a model
holds of its own (its sublayers, its routing, its shares) stands in its
own file."""

import operator

import pytest

from model_cases import (GLM_LITE, KEYE, LAGUNA, MELLUM2, MODELS, NEMOTRON_H, OLMO_HYBRID, SDAR,
                         Model, catalog_config)
from ray_tpu.models.registry import config_from_hf, get_model_config

by_name = pytest.mark.parametrize("model", MODELS, ids=lambda m: m.name)


@by_name
def test_config_from_hf_maps_the_catalogs_config_onto_the_preset(model):
    """The catalog's config is the registry's preset, of the model's own
    configuration class, with a head wider or narrower than d_model /
    heads (where the row states a `head_dim`) and the published values
    the row names."""
    cfg = config_from_hf(catalog_config(model))
    assert cfg == get_model_config(model.preset) and type(cfg) is type(model.fp32)
    if "head_dim" in model.facts:
        assert cfg.head_dim != cfg.d_model // cfg.n_heads
    for field, value in model.facts.items():
        assert operator.attrgetter(field)(cfg) == value, field


@pytest.mark.parametrize("model,key,value,names", [
    (GLM_LITE, "rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    (GLM_LITE, "n_group", 8, "group-limited"),
    (GLM_LITE, "num_nextn_predict_layers", 2, "multi-token-prediction"),
    (GLM_LITE, "attention_bias", True, "attention_bias"),
    (LAGUNA, "moe_router_logit_softcapping", 30.0, "moe_router_logit_softcapping 30.0"),
    (LAGUNA, "moe_apply_router_weight_on_input", True, "moe_apply_router_weight_on_input"),
    (LAGUNA, "gating", True, "gating True"),
    (LAGUNA, "attention_bias", True, "attention_bias"),
    (LAGUNA, "num_attention_heads_per_layer", list(range(48, 96)), "never repeat"),
    (LAGUNA, "mlp_layer_types", ["dense", "sparse", "dense"] + ["sparse"] * 45, "dense layer after"),
    (MELLUM2, "gating", "per-head", "gating 'per-head' .an output gate."),
    (MELLUM2, "attention_bias", True, "attention_bias"),
    (MELLUM2, "mlp_layer_types", ["sparse", "dense"] + ["sparse"] * 26, "dense layer after"),
    (MELLUM2, "rope_parameters", {"full_attention": {"rope_type": "llama3", "rope_theta": 5e5},
                                  "sliding_attention": {"rope_type": "default", "rope_theta": 5e5}},
     "mellum config with rope_type 'llama3'"),
    (MELLUM2, "layer_types", ["sliding_attention", "linear_attention"] * 14,
     "layer_types other than full_attention / sliding_attention"),
    (MELLUM2, "layer_types", ["full_attention"] * 14 + ["sliding_attention"] * 14, "never repeat"),
    (MELLUM2, "use_sliding_window", False, "use_sliding_window false"),
    (MELLUM2, "num_attention_heads_per_layer", [32] * 28, "num_attention_heads_per_layer"),
    (MELLUM2, "shared_expert_intermediate_size", 896, "a shared expert"),
    (MELLUM2, "hidden_act", "gelu", "hidden_act 'gelu'"),
    (SDAR, "rope_scaling", {"rope_type": "yarn", "factor": 4}, "rope_scaling type 'yarn'"),
    (SDAR, "use_sliding_window", True, "a sliding window"),
    (SDAR, "sliding_window", 4096, "a sliding window"),
    (SDAR, "attention_bias", True, "attention_bias"),
    (SDAR, "mlp_only_layers", [0], "mlp_only_layers"),
    (SDAR, "decoder_sparse_step", 2, "decoder_sparse_step 2"),
    (SDAR, "hidden_act", "gelu", "hidden_act 'gelu'"),
    (SDAR, "shared_expert_intermediate_size", 768, "a shared expert"),
    (KEYE, "vision_config", {"depth": 27}, "vision tower or image / video inputs .vision_config."),
    (KEYE, "image_token_id", 151655, "image_token_id"),
    (KEYE, "sa_config", None, "no sa_config"),
    (KEYE, "sa_config", {"indexer_num_heads": 16, "indexer_head_dim": 64,
                         "indexer_num_kv_heads": 2, "topk": 2048}, "indexer_num_kv_heads 2"),
    (KEYE, "rope_scaling", {"rope_type": "yarn", "factor": 4}, "rope_scaling type 'yarn'"),
    (KEYE, "use_sliding_window", True, "a sliding window"),
    (KEYE, "attention_bias", True, "attention_bias"),
    (KEYE, "mlp_only_layers", [0], "mlp_only_layers"),
    (KEYE, "decoder_sparse_step", 2, "decoder_sparse_step 2"),
    (OLMO_HYBRID, "rope_parameters", {"rope_theta": 500000.0}, "rope_theta 500000.0"),
    (OLMO_HYBRID, "linear_num_key_heads", 15, "linear_num_key_heads 15"),
    (OLMO_HYBRID, "num_key_value_heads", 6, "key-value heads other than the query heads"),
    (OLMO_HYBRID, "layer_types", ["linear_attention", "sliding_attention"] * 16,
     "layer_types other than linear_attention / full_attention"),
    (OLMO_HYBRID, "num_hidden_layers", 6, "does not end on a whole period"),
    (OLMO_HYBRID, "attention_bias", True, "attention_bias"),
    (OLMO_HYBRID, "sliding_window", 4096, "a sliding window"),
    (NEMOTRON_H, "hybrid_override_pattern", "M-M*" * 13, "dense MLP layers"),
    (NEMOTRON_H, "hybrid_override_pattern", "MEMX" * 13, "layer kinds other than M, E"),
    (NEMOTRON_H, "num_hidden_layers", 60, "shorter than num_hidden_layers"),
    (NEMOTRON_H, "mamba_proj_bias", True, "a bias"),
    (NEMOTRON_H, "attention_bias", True, "a bias"),
    (NEMOTRON_H, "use_conv_bias", False, "a convolution without a bias"),
    (NEMOTRON_H, "n_group", 8, "n_group 8"),
    (NEMOTRON_H, "sliding_window", 4096, "a sliding window"),
    (NEMOTRON_H, "mlp_hidden_act", "silu", "mlp_hidden_act 'silu'"),
    (NEMOTRON_H, "time_step_limit", [0.0, 0.5], "a clamp on the step"),
], ids=lambda v: v.name if isinstance(v, Model) else None)
def test_config_from_hf_refuses_by_name_what_is_not_implemented(model, key, value, names):
    with pytest.raises(ValueError, match=names):
        config_from_hf({**catalog_config(model), key: value})


@by_name
def test_engine_refuses_the_model_by_name(model):
    from ray_tpu.llm.engine import EngineConfig

    with pytest.raises(ValueError, match=model.refused_as):
        EngineConfig(model=model.tiny)
