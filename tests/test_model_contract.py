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
own file. Two sections compile little and stand here because the file has
the seconds (PR 57; 16 s alone): what a checkpoint and the benchmark's
builders depend on, every tiny preset's seeded tree and axes by digest;
and the table of mixer kinds, models/llama.py::MIXERS."""

import operator

import pytest

from model_cases import (GLM_LITE, KEYE, KIMI_LINEAR, LAGUNA, MELLUM2, MODELS, NEMOTRON_H,
                         OLMO_HYBRID, SDAR, SOLAR_OPEN2, Model, catalog_config)
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
    (SOLAR_OPEN2, "use_rope", True, "use_rope"),
    (SOLAR_OPEN2, "kda_use_full_proj", True, "kda_use_full_proj"),
    (SOLAR_OPEN2, "use_gqa_gate", False, "use_gqa_gate false"),
    # (`kda_allow_neg_eigval` false is no refusal since PR 64: beta = sigmoid is Kimi-Linear's form)
    (KIMI_LINEAR, "mla_use_nope", False, "mla_use_nope false"),
    (SOLAR_OPEN2, "first_k_dense_replace", 1, "first_k_dense_replace 1"),
    (SOLAR_OPEN2, "linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 128,
                                         "num_heads": 64, "num_kv_heads": 8},
     "linear_attn_config.num_kv_heads 8"),
    (SOLAR_OPEN2, "n_shared_experts", 2, "n_shared_experts 2"),
    (SOLAR_OPEN2, "n_group", 8, "n_group 8"),
    (SOLAR_OPEN2, "num_hidden_layers", 6, "does not end on a whole period"),
    (SOLAR_OPEN2, "attention_bias", True, "attention_bias"),
    (SOLAR_OPEN2, "sliding_window", 4096, "a sliding window"),
    (KIMI_LINEAR, "rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    (KIMI_LINEAR, "num_expert_group", 8, "num_expert_group 8"),
    (KIMI_LINEAR, "topk_group", 2, "topk_group 2 .groups of experts."),
    (KIMI_LINEAR, "num_nextn_predict_layers", 1, "num_nextn_predict_layers 1"),
    (KIMI_LINEAR, "q_lora_rank", 768, "q_lora_rank 768"),
    (KIMI_LINEAR, "moe_layer_freq", 2, "moe_layer_freq 2"),
    (KIMI_LINEAR, "moe_router_activation_func", "softmax", "moe_router_activation_func 'softmax'"),
    (KIMI_LINEAR, "num_shared_experts", 2, "num_shared_experts 2"),
    (KIMI_LINEAR, "num_key_value_heads", 8, "num_key_value_heads 8"),
    (KIMI_LINEAR, "attention_bias", True, "attention_bias"),
    (KIMI_LINEAR, "hidden_act", "gelu", "hidden_act 'gelu'"),
    (KIMI_LINEAR, "linear_attn_config", {"short_conv_kernel_size": 4, "head_dim": 128,
                                         "num_heads": 32, "kda_layers": [1, 2, 3],
                                         "full_attn_layers": [3, 4]},
     "do not number every layer once"),
], ids=lambda v: v.name if isinstance(v, Model) else None)
def test_config_from_hf_refuses_by_name_what_is_not_implemented(model, key, value, names):
    with pytest.raises(ValueError, match=names):
        config_from_hf({**catalog_config(model), key: value})


def test_beta_not_doubled_is_read_from_a_solar_open2_config_and_runs():
    """`kda_allow_neg_eigval` false was refused by name until PR 64 built the
    form (Kimi-Linear's): it is now the configuration's `kda_neg_eigval`, and
    the published true stays the preset's."""
    published = catalog_config(SOLAR_OPEN2)
    assert config_from_hf(published).kda_neg_eigval is True
    assert config_from_hf({**published, "kda_allow_neg_eigval": False}).kda_neg_eigval is False
    assert config_from_hf(catalog_config(KIMI_LINEAR)).kda_neg_eigval is False
    assert config_from_hf({**catalog_config(KIMI_LINEAR),
                           "kda_allow_neg_eigval": True}).kda_neg_eigval is True


@by_name
def test_engine_refuses_the_model_by_name(model):
    from ray_tpu.llm.engine import EngineConfig

    with pytest.raises(ValueError, match=model.refused_as):
        EngineConfig(model=model.tiny)


# -- what a checkpoint and the benchmark's builders depend on ---------------------------

TINY_PRESETS = ("llama-tiny", "moe-tiny", "zaya-tiny", "glm-lite-tiny", "laguna-tiny",
                "mellum2-tiny", "sdar-tiny", "keye-tiny", "olmo-hybrid-tiny", "nemotron-h-tiny",
                "solar-open2-tiny", "kimi-linear-tiny", "granite-hybrid-tiny")
# preset -> sha256 of (every leaf's path, shape and dtype), of the leaves' bytes in the paths'
# order, and of (every leaf's path and logical axes), as PR 56's tree (the parent of PR 57,
# which moved WHERE models/llama.py reads a configuration's mixer kind) makes them from
# `jax.random.key(0)`. A change that MEANS to re-lay a tree or to re-seed it replaces the
# row the failure prints, and says which checkpoints and which of chipbench/model_builders/
# it strands.
_TINY_TREES = {
    "llama-tiny": (
        "12b08c6c51a76ff0251b3e92682f1ef08b9986c07ae4a434207aa8c82de3c153",
        "9a9c6362dd44c666ce2392c6acc9698dec124a6238099fd6c2c120494f029680",
        "8736df3e24f3fa7b0490ae88dc7a958d3b480a655d337fd962468b0c459f6faf"),
    "moe-tiny": (
        "7aeb9feaf95ef262e98e7230ec460727587a726a4f628658598597c0130226cf",
        "a887c5ea8bb05269ef9a153497f9818031703c1a3de694ac9f1e6a9e647a7e6e",
        "f57d08af42b0d76afa9ba2238e3db3c2e819a47a7edf57d7fb1674e800fa2f55"),
    "zaya-tiny": (
        "b7cbeab411ee54d93f0a016802e9874f3482094ee86a138a42b176c3e7a7756a",
        "e8eabfd27228ec25db5ce30edeb7770998591fc2ad8ee083726bc6b112e66696",
        "6120e80a2c378ce5ee31dd1d53ecdf6f4d8aedd2a80c5e2b04b0b5a113633270"),
    "glm-lite-tiny": (
        "28b7837aef8f0d02338b7ba84ed0fd73301bf94285c8f5976ebf02bc799e96e0",
        "52b0a91d3a3c345239f080a3a9b5eb9c3f898c986159a3d04534787f17c67097",
        "5cbf29a078309f324d7170650e8ec739dfcf64dea59effd80e2960105c848e92"),
    "laguna-tiny": (
        "a95e98c36291f4581c5d767b42c51b82bad0f83ab713659ddd7a239e73d44f52",
        "9431b972d8e83c6e49a1df9e67bec343f3cd2028b5d961337d27d9900681d6a3",
        "4f7dab57d16b5a9d86272e5321b59792efb5de31c3a531f0189a2af24087e860"),
    "mellum2-tiny": (
        "c479e5b9eea4d392ebe2f6ff566f774982d187419b089eac57540cf3b6a02fd4",
        "8b838beefbfa4bf303d7da6e7d03b9fb0e44971b1c23c2a8a9cd583ccf8823dd",
        "defdadba64045e0053d1be78e7f5c705b84ccf8d5f23c9418f0bd25b262b22c3"),
    "sdar-tiny": (
        "893e1bcb2882a8905b59e9539017cb341cdc027d0874286be58fc06919966c5c",
        "aee5115c885a1fd37cfcadd7d7df6d8fd4e284793df111d6fcbb19a1a26dd7a2",
        "1310c1e985b82d17467a557c5ce35875ac7339d364569f4cabef8a137f7efa44"),
    "keye-tiny": (
        "58d2517c13280885c48c870bbae5a02f627d2f747fef51ea52bd865d02fb5547",
        "0f1f3a59478442e29c43c51afeed7157db4fe000efc8e54f20a8906f1ebc918b",
        "01915fdbc53afa9a84b042181d82c7068fcea09e1037f82c2e7c58e66503cd20"),
    "olmo-hybrid-tiny": (
        "a5148aec9e24195339afb077982f0b26834cfee3682c10ad9a20222b8c569404",
        "d8f097c9ce50c236c6751b87b18a2b3ff0d7589a39f15f27726d95e842661f96",
        "0b0970885095f40edc77f077244510d697c95d0a3cb4db43c3a7953dbe6c5f94"),
    "nemotron-h-tiny": (
        "67e89bb9c55041d4558b8ea1be5da6602f28da2643a74de9296380f7bf998aa5",
        "8f5a4d6708400392896714483bd6f1efe07b9539983a511dc806c2f6cdf0e9e0",
        "52a3880e1e46087ffda639c86f1502f768136575a2e141cd49c78becc9d52f90"),
    # as PR 60 made it (models/solar_open2.py)
    "solar-open2-tiny": (
        "68c72f7f46fb31463d62f89cb6787a5de75e2a1aad916f2fa82e468228a1deea",
        "013de14d5186164a99cdb501970d4cd72dea75228b657d71f8b31cf524e70b47",
        "69b588ab7eb833ded4f755ccebe8d5077159400fb02bd720fefc16fa2e44a20d"),
    # as PR 64 made it (models/kimi_linear.py)
    "kimi-linear-tiny": (
        "928f16eb4d7586535037741561032a71e42a5e931d82595ed3d1a2dfdf4c4502",
        "957d5d954833a08f386ebc0aec461583d2e5d10603a88e97b8ee84aa939e70d0",
        "c2167b5181965f594d25913eae02763733c066af57fb8a1c4c80fa6840325c5a"),
    # as PR 66 made it (models/granite_hybrid.py: a group a segment of the stack)
    "granite-hybrid-tiny": (
        "716fc6642bc42035dfe4251ee3feb98d1e633118406f1d24a7ff17aba93ac31d",
        "347552f9372c76dfa9932f926b43bcb7dc582d330d00ca7e7d67873977dc328f",
        "16349c76bf23155566812f73c29cd6d7f6188fbdd012b735600d4f95aed4441a"),
}


def _tree_digests(cfg) -> tuple:
    import hashlib

    import jax
    import numpy as np

    from ray_tpu.models import llama

    def by_path(tree, **kw):
        return sorted((jax.tree_util.keystr(p), v)
                      for p, v in jax.tree_util.tree_leaves_with_path(tree, **kw))

    def digest(lines):
        return hashlib.sha256("\n".join(lines).encode()).hexdigest()

    leaves = [(p, np.asarray(v)) for p, v in by_path(llama.init_params(cfg, jax.random.key(0)))]
    values = hashlib.sha256()
    for _, v in leaves:
        values.update(np.ascontiguousarray(v).tobytes())
    axes = by_path(llama.logical_axes(cfg), is_leaf=lambda a: isinstance(a, tuple))
    return (digest(f"{p} {v.shape} {v.dtype}" for p, v in leaves), values.hexdigest(),
            digest(f"{p} {a}" for p, a in axes))


@pytest.mark.parametrize("preset", TINY_PRESETS)
def test_tiny_preset_seeds_the_tree_and_names_the_axes_it_did(preset):
    """`init_params(c, key(0))` gives the tree it gave (paths, shapes,
    dtypes, every leaf's bytes) and `logical_axes(c)` the axes."""
    got = _tree_digests(get_model_config(preset))
    assert got == _TINY_TREES.get(preset), f'    "{preset}": {got!r},'


# -- the table of mixer kinds (models/llama.py::MIXERS) ---------------------------------


def test_every_registered_configuration_names_a_row_of_the_table():
    """The kind is the configuration CLASS's: no field, so a
    configuration's repr, equality and hash are what they were."""
    import dataclasses

    from ray_tpu.models import llama
    from ray_tpu.models.registry import list_models

    kinds = {"LlamaConfig": "gqa", "MoEConfig": "gqa", "ZayaConfig": "cca",
             "GlmLiteConfig": "mla", "KeyeConfig": "dsa"}
    for name in list_models():
        cfg = get_model_config(name)
        assert llama._mixer(cfg) is llama.MIXERS[cfg.mixer], name
        assert "mixer" not in {f.name for f in dataclasses.fields(cfg)}, name
        assert "mixer" not in repr(cfg), name
        if type(cfg).__name__ in kinds:   # the others' stacks are their `stack_module`'s
            assert cfg.mixer == kinds[type(cfg).__name__], name
    assert set(llama.MIXERS) == set(kinds.values())


def test_an_unknown_mixer_kind_is_refused_by_name():
    from ray_tpu.models import llama

    class Odd(llama.LlamaConfig):
        mixer = "odd"

    import jax

    odd = Odd(vocab_size=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=1, d_ff=16, max_seq=8)
    for build in (llama.logical_axes, lambda c: llama.init_params(c, jax.random.key(0))):
        with pytest.raises(ValueError, match="unknown mixer kind 'odd'.*cca.*dsa.*gqa.*mla"):
            build(odd)


def test_the_names_remat_can_save_are_the_ten_it_saved():
    """Every row's, the names that are no mixer's, and what the two
    `stack_module` modules with kernels of their own declare."""
    from ray_tpu.models import laguna, llama, nemotron_h, olmo_hybrid

    saved = set()
    for cfg in (llama.LLAMA_TINY, laguna.LAGUNA_TINY, olmo_hybrid.OLMO_HYBRID_TINY,
                nemotron_h.NEMOTRON_H_TINY):
        saved |= llama.remat_saves(cfg)
    assert llama.remat_saves(llama.LLAMA_TINY) == {
        "attn_out", "attn_lse", "tp_rs_out", "moe_gate", "moe_up", "dsa_sel"}
    assert saved == {"attn_out", "attn_lse", "tp_rs_out", "moe_gate", "moe_up", "dsa_sel",
                     "gdn_out", "gdn_states", "ssd_out", "ssd_states"}
