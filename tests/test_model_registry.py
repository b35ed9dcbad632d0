"""Model registry: named presets + HF config.json mapping.

Reference analog: serving any HF model id through vLLM's loader; here
the llama/mixtral families map onto the native decoders and everything
else is rejected loudly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.models.registry import (
    config_from_hf,
    get_model_config,
    list_models,
    register_model,
)


def test_presets_resolve_and_are_consistent():
    assert "llama3-8b" in list_models()
    cfg = get_model_config("LLAMA3-8B")  # case-insensitive
    assert cfg.d_model == 4096 and cfg.n_layers == 32
    m7 = get_model_config("mistral-7b")
    assert m7.d_ff == 14336 and m7.n_kv_heads == 8
    mx = get_model_config("mixtral-8x7b")
    assert isinstance(mx, moe.MoEConfig)
    with pytest.raises(KeyError):
        get_model_config("nope-13b")
    with pytest.raises(ValueError):
        register_model("llama3-8b", cfg)  # duplicate


def test_hf_llama_mapping_runs_forward():
    hf = {
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "max_position_embeddings": 128,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True,
    }
    cfg = config_from_hf(hf, remat=False)
    assert cfg.n_kv_heads == 2 and cfg.tie_embeddings
    params = llama.init_params(cfg, jax.random.key(0))
    logits = llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    assert logits.shape == (1, 8, 512)


def test_hf_mixtral_mapping():
    hf = {
        "architectures": ["MixtralForCausalLM"],
        "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "num_local_experts": 4,
        "num_experts_per_tok": 2,
    }
    cfg = config_from_hf(hf)
    assert isinstance(cfg, moe.MoEConfig)
    assert cfg.n_experts == 4 and cfg.top_k == 2


def test_hf_olmoe_mapping_of_the_catalogs_config():
    """The public config.json of allenai/OLMoE-1B-7B-0125-Instruct as the
    model-configs catalog has it (no `architectures` key, `model_type`
    olmoe), and the same with HF's architectures field: both are the
    registry's preset."""
    hf = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
    }
    cfg = config_from_hf(hf)
    assert cfg == get_model_config("olmoe-1b-7b")
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff) == (64, 8, 1024)  # d_ff: ONE expert's width
    assert cfg.qk_norm and not cfg.norm_topk_prob
    assert (cfg.router_aux_coeff, cfg.router_z_coeff) == (0.01, 0.001)
    assert cfg.num_params() == pytest.approx(6.92e9, rel=0.01)  # "1B-7B": 6.9B in all
    assert config_from_hf({**hf, "architectures": ["OlmoeForCausalLM"]}) == cfg
    assert config_from_hf({**hf, "norm_topk_prob": True}).norm_topk_prob
    with pytest.raises(ValueError, match="clip_qkv"):
        config_from_hf({**hf, "clip_qkv": 8.0})


def test_flops_per_token_counts_attention_and_only_the_active_experts():
    """Forward FLOPs a token, by hand: mistral-7b at sequence 4096 and
    olmoe-1b-7b (a token runs 8 of its 64 experts, and the router)."""
    m7 = get_model_config("mistral-7b")
    layer = 2 * 4096 * (4096 + 2 * 1024 + 4096) + 3 * 2 * 4096 * 14336 + 4 * 128 * 32 * 4097 / 2
    assert m7.flops_per_token(4096) == 32 * layer + 2 * 4096 * 32000
    ol = get_model_config("olmoe-1b-7b")
    layer = (2 * 2048 * 4 * 2048 + 8 * 3 * 2 * 2048 * 1024 + 2 * 2048 * 64
             + 4 * 128 * 16 * 4097 / 2)
    assert ol.flops_per_token(4096) == 16 * layer + 2 * 2048 * 50304


def test_hf_unknown_architecture_rejected():
    with pytest.raises(ValueError, match="unsupported architectures"):
        config_from_hf({
            "architectures": ["GPTBigCodeForCausalLM"],
            "vocab_size": 1, "hidden_size": 8, "num_hidden_layers": 1,
            "num_attention_heads": 1, "intermediate_size": 8,
        })


def test_engine_accepts_model_name():
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    cfg = EngineConfig(model="llama-tiny", num_blocks=32, block_size=4,
                       max_num_seqs=2)
    assert cfg.model.d_model == 64
    eng = LLMEngine(cfg)
    out = eng.generate([[5, 6, 7]],
                       SamplingParams(max_tokens=4, ignore_eos=True))[0]
    assert len(out) == 4


# Zyphra/ZAYA1-8B config.json as the model-configs catalog's row gives it (PR 32)
ZAYA1_HF = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts": 16,
    "num_experts_per_tok": 1, "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True,
    "vocab_size": 262272,
}


def test_hf_zaya_mapping_of_the_catalogs_config():
    from ray_tpu.models import cca

    cfg = config_from_hf(ZAYA1_HF)
    assert isinstance(cfg, cca.ZayaConfig) and cfg == get_model_config("zaya1-8b")
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 8, 2, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.router_kind, cfg.router_hidden) == (
        16, 1, 2048, "mlp", 256)
    assert cfg.conv_kernels == (2, 2) and cfg.rotary_fraction == 0.5 and cfg.rope_theta == 5e6
    assert cfg.tie_embeddings and cfg.n_layers == 40 and cfg.n_held == 16
    assert not cfg.norm_topk_prob and cfg.router_aux_coeff == 0.0 == cfg.router_z_coeff
    # 8.3B without the table, 0.54B in it; 2.9 GFLOP a token forward, the head 36.8% of it
    assert cfg.num_params() == pytest.approx(8.84e9, rel=2e-3)
    assert 2 * 2048 * 262272 / cfg.flops_per_token(4096) == pytest.approx(0.368, abs=0.003)
    share = config_from_hf(ZAYA1_HF, n_layers=6, vocab_size=32896, experts_held=8)
    assert share.num_params() == pytest.approx(708.8e6, rel=1e-3) and share.n_held == 8


@pytest.mark.parametrize("key,value,named", [
    ("sliding_window", 4096, "sliding_window"),
    ("layer_types", ["hybrid", "hybrid_sliding"], "layer_types"),
    ("attention_bias", True, "attention_bias"), ("lm_head_bias", True, "lm_head_bias"),
    ("hidden_act", "gelu", "hidden_act 'gelu'")])
def test_hf_zaya_refuses_by_name_what_it_does_not_implement(key, value, named):
    with pytest.raises(ValueError, match=named):
        config_from_hf({**ZAYA1_HF, key: value})


def test_zaya_tiny_runs_forward_and_counts_its_sites():
    from ray_tpu import obs

    cfg = get_model_config("zaya-tiny")
    params = llama.init_params(cfg, jax.random.key(0))
    before = obs.layer_counters()
    logits = llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    after = obs.layer_counters()
    assert logits.shape == (1, 8, 512)
    for name in ("cca.attn", "moe.ffn"):  # one site a scanned block, while tracing
        assert after[name]["count"] - before.get(name, {"count": 0})["count"] >= 1
