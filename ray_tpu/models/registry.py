"""Model registry: named presets + HuggingFace config mapping.

Reference analog: the reference serves any HF model id by delegating to
vLLM's model loader (llm/_internal/serve/deployments/llm/vllm/
vllm_models.py model_id plumbing). This framework's compute path is the
llama-family decoder (models/llama.py — which covers Llama 1/2/3,
Mistral, Qwen2, TinyLlama, ... since they share the architecture), which
runs a sparse expert layer in place of its MLP for a MoEConfig
(models/moe.py — Mixtral; OLMoE with its q/k norm and unnormalised
top-8 of 64; ZAYA1-8B, `zaya1-8b`, with compressed convolutional
attention in place of the block's own (models/cca.py), an MLP router
whose state is carried from layer to layer and top-1 of 16 experts, of
which a chip may hold a share; GLM-4.7-Flash, `glm-4.7-flash`, with
multi-head latent attention at heads of 256 (models/mla.py), a leading
dense layer, sigmoid top-4 of 64 experts chosen with a selection bias,
a shared expert and a multi-token-prediction block; Laguna-S-2.1,
`laguna-s-2.1`, trained, not served: sliding-window (512) and full
attention layers of 72 and 48 heads of an explicit 128 in one stack
(models/laguna.py), a headwise output gate, a rotary by layer type (yarn
on half a head in the full layers), a leading dense layer, softmax
top-10 of 256 experts chosen with a selection bias, weights renormalised
and scaled, and a shared expert; Mellum2-12B-A2.5B, `mellum2-12b-a2.5b`,
trained, not served, through that same module: three sliding-window
(1024) layers to one full layer at ONE head count (GQA 32 / 4 of an
explicit 128), an RMSNorm a head on q and k, no gate, yarn over the whole
head of the full layers, no dense layer, softmax top-8 of 64 experts
chosen with a selection bias and renormalised, no shared expert;
SDAR-30B-A3B, `sdar-30b-a3b`, through that same module, TRAINED BY BLOCK
DIFFUSION, not served, not generated from: 48 full-attention layers alike
at GQA 32 / 4 of an explicit 128 with the norm a head, no window, softmax
top-8 of 128 experts of width 768 renormalised, no shared expert; a
training step runs a clean and a noised copy of every sequence under a
mask of blocks of 4 positions (ops/flash.py's `blockdiff`) and takes a
weighted denoising loss over the masked positions
(models/block_diffusion.py); the
language model of
Keye-VL-2.0-30B-A3B, `keye-vl-2.0-30b-a3b`, trained, not served: GQA 32 /
4 at heads of an explicit 128 with an RMSNorm a head on q and k, over the
2048 keys a query that a learned indexer of 16 heads of 64 selects
(models/dsa.py, loaded only when one of its names is asked for), softmax
top-8 of 128 experts chosen with a selection bias and renormalised; its
vision tower and image or video inputs are refused by name. Every expert
configuration is TRAINING only: the serving engine refuses a MoEConfig,
ZAYA1, GLM-4.7-Flash, Laguna, Mellum2, SDAR and Keye by name), and which runs a stack of
its own for Olmo-Hybrid-7B, `olmo-hybrid-7b`, trained, not served: three
gated-delta-rule linear-attention layers (30 heads, keys of 96, values of
192, a causal convolution of 4; models/olmo_hybrid.py and
ops/gated_delta.py, loaded only when one of its names is asked for) to one
full-attention layer without a rotary, each over a dense SwiGLU, each
sublayer's output normed; and for the causal tower of
Nemotron-Labs-TwoTower-30B-A3B, `nemotron-twotower-30b-a3b`, trained, not
served: Mamba-2 state-space mixers (64 heads of 64, a state of 128, 8
groups; models/nemotron_h.py, ops/ssd.py and ops/gdn_conv.py's kernels
with a bias), 128 relu^2 experts of two matrices (sigmoid top-6, one
shared) and GQA 32 / 2 without a rotary, ONE sublayer a layer by the
published pattern string; its second (denoiser) tower and diffusion
objective are not built; and for Solar-Open2-250B, `solar-open2-250b`,
trained, not served: three Kimi-Delta-Attention layers (a delta rule
whose decay is a vector over the key's channels, 64 heads of 128;
models/solar_open2.py over ops/kda.py's two Pallas kernels a layer) to
one gated GQA 64 / 8 layer
without a rotary, each over 320 sigmoid-routed experts of 1280 (top-8)
and a shared one; and for Kimi-Linear-48B-A3B, `kimi-linear-48b-a3b`,
trained, not served: Kimi-Delta-Attention layers at 32 heads of 128 (beta
NOT doubled) three to one latent-attention (MLA) layer without a rotary
and without a query latent, keys of 192 beside values of 128
(models/kimi_linear.py over models/solar_open2.py's KDA sublayer,
models/mla.py's MLA sublayer and ops/flash.py at a value width of its own),
a leading dense layer of 9216, then 256 sigmoid-routed experts of 1024
(top-8, x 2.446) and a shared one; the stack ends inside a period; and for
Granite 4.0-H Micro, `granite-4.0-h-micro`, trained, not served: nine
Mamba-2 mixers (64 heads of 64 in ONE group, a state of 128) to one GQA
32 / 8 layer without a rotary at scale 1 / 64, each over a dense SwiGLU of
8192, four muP multipliers and a tied table (models/granite_hybrid.py over
models/nemotron_h.py's two sublayers), trained on packed documents
(`segment_ids` through ops/ssd.py, ops/gdn_conv.py and ops/flash.py).
The registry gives users the same two entry points they expect:

  * `get_model_config("llama3-8b")` — named presets;
  * `config_from_hf(json.load(open("config.json")))` — map a HF
    transformers config dict onto LlamaConfig/MoEConfig/ZayaConfig/
    GlmLiteConfig/LagunaConfig/KeyeConfig/OlmoHybridConfig/NemotronHConfig/
    SolarOpen2Config/KimiLinearConfig/GraniteHybridConfig (no downloads;
    weight conversion is a separate concern).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any

from ray_tpu.models import cca, laguna, llama, mla, moe

_REGISTRY: dict[str, Any] = {}
# name -> (module, attribute): presets of a module that only their own users load
_ON_DEMAND = {"keye-vl-2.0-30b-a3b": ("ray_tpu.models.dsa", "KEYE_VL_2_30B_A3B"),
              "keye-tiny": ("ray_tpu.models.dsa", "KEYE_TINY"),
              "olmo-hybrid-7b": ("ray_tpu.models.olmo_hybrid", "OLMO_HYBRID_7B"),
              "olmo-hybrid-tiny": ("ray_tpu.models.olmo_hybrid", "OLMO_HYBRID_TINY"),
              "nemotron-twotower-30b-a3b": ("ray_tpu.models.nemotron_h",
                                            "NEMOTRON_TWOTOWER_30B_A3B"),
              "nemotron-h-tiny": ("ray_tpu.models.nemotron_h", "NEMOTRON_H_TINY"),
              "solar-open2-250b": ("ray_tpu.models.solar_open2", "SOLAR_OPEN2_250B"),
              "solar-open2-tiny": ("ray_tpu.models.solar_open2", "SOLAR_OPEN2_TINY"),
              "kimi-linear-48b-a3b": ("ray_tpu.models.kimi_linear", "KIMI_LINEAR_48B_A3B"),
              "kimi-linear-tiny": ("ray_tpu.models.kimi_linear", "KIMI_LINEAR_TINY"),
              "granite-4.0-h-micro": ("ray_tpu.models.granite_hybrid", "GRANITE_4_H_MICRO"),
              "granite-hybrid-tiny": ("ray_tpu.models.granite_hybrid", "GRANITE_HYBRID_TINY")}


def register_model(name: str, config) -> None:
    key = name.lower()
    if key in _REGISTRY or key in _ON_DEMAND:
        raise ValueError(f"model {name!r} already registered")
    _REGISTRY[key] = config


def get_model_config(name: str):
    """Named preset lookup (case-insensitive); returns a frozen config."""
    key = name.lower()
    if key in _ON_DEMAND:
        module, attribute = _ON_DEMAND[key]
        return getattr(importlib.import_module(module), attribute)
    try:
        return _REGISTRY[key]
    except KeyError:
        raise KeyError(
            f"unknown model {name!r}; registered: {list_models()}"
        ) from None


def list_models() -> list[str]:
    return sorted([*_REGISTRY, *_ON_DEMAND])


# -- presets (architecture hyperparameters from the public model cards) ------

for _name, _cfg in {
    "llama3-8b": llama.LLAMA3_8B,
    "llama3-1b": llama.LLAMA3_1B,
    "llama-400m": llama.LLAMA_400M,
    "llama-tiny": llama.LLAMA_TINY,
    "llama3-70b": dataclasses.replace(
        llama.LLAMA3_8B, d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        d_ff=28672,
    ),
    "mistral-7b": dataclasses.replace(
        llama.LLAMA3_8B, vocab_size=32000, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, rope_theta=10000.0,
        max_seq=32768,
    ),
    "qwen2-7b": dataclasses.replace(
        llama.LLAMA3_8B, vocab_size=152064, d_model=3584, n_layers=28,
        n_heads=28, n_kv_heads=4, d_ff=18944, rope_theta=1000000.0,
        max_seq=32768,
    ),
    "tinyllama-1.1b": dataclasses.replace(
        llama.LLAMA3_8B, vocab_size=32000, d_model=2048, n_layers=22,
        n_heads=32, n_kv_heads=4, d_ff=5632, rope_theta=10000.0,
        max_seq=2048,
    ),
    "mixtral-8x7b": moe.MIXTRAL_8X7B,
    "olmoe-1b-7b": moe.OLMOE_1B_7B,
    "moe-tiny": moe.MOE_TINY,
    "zaya1-8b": cca.ZAYA1_8B,
    "zaya-tiny": cca.ZAYA_TINY,
    "glm-4.7-flash": mla.GLM_4_7_FLASH,
    "glm-lite-tiny": mla.GLM_LITE_TINY,
    "laguna-s-2.1": laguna.LAGUNA_S_2_1,
    "laguna-tiny": laguna.LAGUNA_TINY,
    "mellum2-12b-a2.5b": laguna.MELLUM2_12B_A2_5B,
    "mellum2-tiny": laguna.MELLUM2_TINY,
    "sdar-30b-a3b": laguna.SDAR_30B_A3B,
    "sdar-tiny": laguna.SDAR_TINY,
}.items():
    register_model(_name, _cfg)


# -- HF transformers config.json mapping -------------------------------------

_HF_LLAMA_ARCHS = {
    "LlamaForCausalLM", "MistralForCausalLM", "Qwen2ForCausalLM",
}
_HF_MOE_ARCHS = {"MixtralForCausalLM", "OlmoeForCausalLM"}


def _zaya_from_hf(hf: dict, **overrides) -> cca.ZayaConfig:
    """`model_type` "zaya" (Zyphra/ZAYA1-8B): every layer CCA then the
    routed experts. What this decoder does not implement is refused by
    name, not mapped onto something near it."""
    rope = hf.get("rope_parameters", {}).get("hybrid", {})
    refused = {
        "sliding_window": hf.get("sliding_window") is not None,
        "layer_types other than 'hybrid'": any(
            t != "hybrid" for t in hf.get("layer_types", ())),
        "attention_bias": bool(hf.get("attention_bias")),
        "lm_head_bias": bool(hf.get("lm_head_bias")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
        f"rope_type {rope.get('rope_type')!r}": rope.get("rope_type", "default") != "default",
    }
    if any(refused.values()):
        raise ValueError("a zaya config with " + ", ".join(k for k, v in refused.items() if v)
                         + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["moe_intermediate_size"],
        max_seq=hf["max_position_embeddings"],
        rope_theta=float(rope.get("rope_theta", 10000.0)),
        rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        router_hidden=hf["router_hidden_size"], latent_head_dim=hf["head_dim"],
        conv_kernels=(hf["cca_time0"], hf["cca_time1"]),
        rotary_fraction=float(rope.get("partial_rotary_factor",
                                       hf.get("partial_rotary_factor", 1.0))),
    )
    fields.update(overrides)  # caller wins on collisions
    return dataclasses.replace(cca.ZAYA1_8B, **fields)


def _glm_lite_from_hf(hf: dict, **overrides) -> mla.GlmLiteConfig:
    """`model_type` "glm4_moe_lite" (zai-org/GLM-4.7-Flash): MLA with the
    explicit head sizes, `first_k_dense_replace` dense layers, then sigmoid
    top-k routed experts beside `n_shared_experts` shared ones, and
    `num_nextn_predict_layers` multi-token-prediction blocks. What this
    decoder does not implement is refused by name."""
    refused = {
        f"rope_scaling {hf.get('rope_scaling')!r}": hf.get("rope_scaling") is not None,
        f"n_group {hf.get('n_group')} (group-limited routing)": hf.get("n_group", 1) > 1,
        f"topk_method {hf.get('topk_method')!r}": hf.get("topk_method", "noaux_tc") != "noaux_tc",
        "attention_bias": bool(hf.get("attention_bias")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
        f"partial_rotary_factor {hf.get('partial_rotary_factor')}":
            hf.get("partial_rotary_factor", 1) != 1,
        "q_lora_rank null": hf.get("q_lora_rank") is None,
        f"{hf.get('num_nextn_predict_layers')} multi-token-prediction blocks":
            hf.get("num_nextn_predict_layers", 0) > 1,
        "key-value heads other than the query heads":
            hf.get("num_key_value_heads", hf["num_attention_heads"]) != hf["num_attention_heads"],
    }
    if any(refused.values()):
        raise ValueError("a glm4_moe_lite config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_attention_heads"], d_ff=hf["moe_intermediate_size"],
        max_seq=hf["max_position_embeddings"], rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["n_routed_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling=float(hf["routed_scaling_factor"]),
        shared_d_ff=hf.get("n_shared_experts", 0) * hf["moe_intermediate_size"],
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"], qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], dense_d_ff=hf["intermediate_size"],
        first_dense_layers=hf.get("first_k_dense_replace", 0),
        mtp_layers=hf.get("num_nextn_predict_layers", 0),
    )
    fields.update(overrides)  # caller wins on collisions
    return dataclasses.replace(mla.GLM_4_7_FLASH, **fields)


def _rotary_from_hf(group: dict, family: str = "laguna") -> laguna.Rotary:
    kind = group.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"a {family} config with rope_type {kind!r} is not supported")
    fields = dict(theta=float(group["rope_theta"]), rope_type=kind,
                  partial=float(group.get("partial_rotary_factor", 1.0)))
    if kind == "yarn":
        fields.update(factor=float(group["factor"]),
                      original_max=int(group["original_max_position_embeddings"]),
                      beta_fast=float(group.get("beta_fast", 32)),
                      beta_slow=float(group.get("beta_slow", 1)),
                      attention_factor=float(group["attention_factor"]))
    return laguna.Rotary(**fields)


def _laguna_from_hf(hf: dict, **overrides) -> laguna.LagunaConfig:
    """`model_type` "laguna" (poolside/Laguna-S-2.1), through the stack
    models/laguna.py as it runs now (Laguna's kind of it: head counts by
    layer, the gate, a leading dense layer, a shared expert;
    `_mellum_from_hf` maps the other): sliding-window and
    full attention layers (`layer_types`) at their own head counts
    (`num_attention_heads_per_layer`) and an explicit `head_dim`, a gate a
    head on the attention's output, a rotary by layer type
    (`rope_parameters`), dense layers where `mlp_layer_types` says
    (leading ones only), softmax top-k routed experts beside a shared
    one. What this decoder does not implement is refused by name; that
    the stack is periodic (models/laguna.py::plan finds the period, of any
    length, and a tail) is checked last."""
    n = hf["num_hidden_layers"]
    mlp = list(hf.get("mlp_layer_types") or ["dense" if l in hf.get("mlp_only_layers", ())
                                             else "sparse" for l in range(n)])
    first_dense = next((l for l, t in enumerate(mlp) if t != "dense"), n)
    heads = list(hf.get("num_attention_heads_per_layer") or [hf["num_attention_heads"]] * n)
    types = list(hf.get("layer_types") or ["full_attention"] * n)
    refused = {
        f"moe_router_logit_softcapping {hf.get('moe_router_logit_softcapping')}":
            bool(hf.get("moe_router_logit_softcapping")),
        "moe_apply_router_weight_on_input": bool(hf.get("moe_apply_router_weight_on_input")),
        f"gating {hf.get('gating')!r} (per-head is implemented)":
            hf.get("gating") != "per-head"
            or any(g != "per_head" for g in hf.get("gating_types", ())),
        "a dense layer after a sparse one": "dense" in mlp[first_dense:],
        "layer_types other than full_attention / sliding_attention":
            any(t not in (laguna.FULL, laguna.SLIDING) for t in types),
        "layer_types / num_attention_heads_per_layer shorter than num_hidden_layers":
            len(types) < n or len(heads) < n,
        "attention_bias": bool(hf.get("attention_bias")),
        f"decoder_sparse_step {hf.get('decoder_sparse_step')}":
            hf.get("decoder_sparse_step", 1) != 1,
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
    }
    if any(refused.values()):
        raise ValueError("a laguna config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    rope = hf["rope_parameters"]
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["moe_intermediate_size"], max_seq=hf["max_position_embeddings"],
        rope_theta=float(rope[laguna.FULL]["rope_theta"]), rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling=float(hf.get("moe_routed_scaling_factor", 1.0)),
        shared_d_ff=hf.get("shared_expert_intermediate_size", 0), head_dim=hf["head_dim"],
        layer_types=tuple(types), heads_per_layer=tuple(heads),
        sliding_window=hf["sliding_window"],
        rope_full=_rotary_from_hf(rope[laguna.FULL]),
        rope_sliding=_rotary_from_hf(rope[laguna.SLIDING]),
        first_dense_layers=first_dense, dense_d_ff=hf["intermediate_size"],
    )
    fields.update(overrides)  # caller wins on collisions
    config = dataclasses.replace(laguna.LAGUNA_S_2_1, **fields)
    if laguna.plan(config)["periods"] < 2:
        # it would run, every layer unrolled in one program: not what the scan is for
        raise ValueError("a laguna config whose layer_types / num_attention_heads_per_layer "
                         "never repeat (not periodic) is not supported")
    return config


def _mellum_from_hf(hf: dict, **overrides) -> laguna.LagunaConfig:
    """`model_type` "mellum" (JetBrains/Mellum2-12B-A2.5B-Instruct) onto
    models/laguna.py's stack: sliding-window and full attention layers
    (`layer_types`: what is read; `max_window_layers` says nothing beside
    it) at ONE head count and an explicit `head_dim`, an RMSNorm a head on
    q and k (ASSUMED from the Qwen3-MoE lineage of the config's keys: it
    has none for it), no gate, a rotary by layer type (`rope_parameters`),
    every layer sparse (leading dense ones would run; none is published),
    softmax top-k experts renormalised by `norm_topk_prob`, no shared
    expert. What the stack does not implement is refused by name."""
    n = hf["num_hidden_layers"]
    mlp = list(hf.get("mlp_layer_types") or ["sparse"] * n)
    first_dense = next((l for l, t in enumerate(mlp) if t != "dense"), n)
    types = list(hf.get("layer_types") or ["full_attention"] * n)
    rope = hf["rope_parameters"]
    refused = {
        f"gating {hf.get('gating')!r} (an output gate)": bool(hf.get("gating")),
        "attention_bias": bool(hf.get("attention_bias")),
        "a dense layer after a sparse one (mlp_layer_types not all sparse but leading dense)":
            "dense" in mlp[first_dense:],
        "layer_types other than full_attention / sliding_attention":
            any(t not in (laguna.FULL, laguna.SLIDING) for t in types),
        "layer_types shorter than num_hidden_layers": len(types) < n,
        "use_sliding_window false beside sliding layer_types":
            not hf.get("use_sliding_window", True) and laguna.SLIDING in types,
        "num_attention_heads_per_layer": bool(hf.get("num_attention_heads_per_layer")),
        "a shared expert": bool(hf.get("shared_expert_intermediate_size")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
    }
    if any(refused.values()):
        raise ValueError("a mellum config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["moe_intermediate_size"], max_seq=hf["max_position_embeddings"],
        rope_theta=float(rope[laguna.FULL]["rope_theta"]), rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]), head_dim=hf["head_dim"],
        layer_types=tuple(types), sliding_window=hf["sliding_window"],
        rope_full=_rotary_from_hf(rope[laguna.FULL], "mellum"),
        rope_sliding=_rotary_from_hf(rope[laguna.SLIDING], "mellum"),
        first_dense_layers=first_dense, dense_d_ff=hf["intermediate_size"],
    )
    fields.update(overrides)  # caller wins on collisions
    config = dataclasses.replace(laguna.MELLUM2_12B_A2_5B, **fields)
    if laguna.plan(config)["periods"] < 2:
        raise ValueError("a mellum config whose layer_types never repeat (not periodic) "
                         "is not supported")
    return config


def _sdar_from_hf(hf: dict, **overrides) -> laguna.LagunaConfig:
    """`model_type` "sdar_moe" (JetLM/SDAR-30B-A3B-Chat) onto
    models/laguna.py's stack: the Qwen3-MoE block in every layer, full
    attention at ONE head count and an explicit `head_dim`, an RMSNorm a
    head on q and k (ASSUMED from the lineage, as Mellum2's and Keye's),
    the plain rotary at `rope_theta`, softmax top-k experts renormalised
    by `norm_topk_prob`, no shared expert; trained by block diffusion in
    blocks of 4 positions (ASSUMED: the release's default; the config has
    no key for the block length or the noise schedule, and an override
    `diffusion_block=` names another). What the stack does not implement
    is refused by name."""
    n = hf["num_hidden_layers"]
    scaling = hf.get("rope_scaling") or {}
    refused = {
        f"rope_scaling type {scaling.get('rope_type', scaling.get('type'))!r}":
            scaling.get("rope_type", scaling.get("type", "default")) != "default",
        "a sliding window": bool(hf.get("use_sliding_window")) or hf.get("sliding_window") is not None,
        "attention_bias": bool(hf.get("attention_bias")),
        f"decoder_sparse_step {hf.get('decoder_sparse_step')}": hf.get("decoder_sparse_step", 1) != 1,
        f"mlp_only_layers {hf.get('mlp_only_layers')}": bool(hf.get("mlp_only_layers")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
        "a shared expert": bool(hf.get("shared_expert_intermediate_size")),
    }
    if any(refused.values()):
        raise ValueError("a sdar_moe config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    rotary = laguna.Rotary(float(hf["rope_theta"]))
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["moe_intermediate_size"], max_seq=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]), rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]), head_dim=hf["head_dim"],
        layer_types=(laguna.FULL,) * n, rope_full=rotary, rope_sliding=rotary,
        dense_d_ff=hf["intermediate_size"],
    )
    fields.update(overrides)  # caller wins on collisions
    return dataclasses.replace(laguna.SDAR_30B_A3B, **fields)


def _keye_from_hf(hf: dict, **overrides):
    """`model_type` "KeyeVL2" (Kwai-Keye/Keye-VL-2.0-30B-A3B): the LANGUAGE
    model's keys, at the top level or under `text_config`: GQA at an
    explicit `head_dim` over the keys `sa_config`'s indexer selects, every
    layer routed experts (softmax top-k, renormalised, no shared one).
    What this decoder does not implement is refused by name: the vision
    tower and image or video inputs (`mrope_section` is accepted for TEXT,
    whose three position streams are equal, so that the sectioned rotary
    is the plain one; position streams that differ are not run)."""
    from ray_tpu.models import dsa

    vision = {k: hf[k] for k in ("vision_config", "image_token_id", "video_token_id",
                                 "vision_start_token_id") if hf.get(k) is not None}
    hf = {**hf, **hf.get("text_config", {})}
    sa = hf.get("sa_config") or {}
    scaling = hf.get("rope_scaling") or {}
    refused = {
        "a vision tower or image / video inputs (" + ", ".join(vision) + "): position streams "
        "that differ and a tower before the decoder are not implemented": bool(vision),
        "no sa_config (the indexer's sizes)": not sa,
        f"indexer_num_kv_heads {sa.get('indexer_num_kv_heads')} (one shared key is implemented)":
            sa.get("indexer_num_kv_heads", 1) != 1,
        f"rope_scaling type {scaling.get('rope_type', scaling.get('type'))!r}":
            scaling.get("rope_type", scaling.get("type", "default")) != "default",
        "a sliding window": bool(hf.get("use_sliding_window")) or hf.get("sliding_window") is not None,
        "attention_bias": bool(hf.get("attention_bias")),
        f"decoder_sparse_step {hf.get('decoder_sparse_step')}": hf.get("decoder_sparse_step", 1) != 1,
        f"mlp_only_layers {hf.get('mlp_only_layers')}": bool(hf.get("mlp_only_layers")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
        "a shared expert": bool(hf.get("shared_expert_intermediate_size")),
    }
    if any(refused.values()):
        raise ValueError("a KeyeVL2 config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["moe_intermediate_size"],
        max_seq=hf["max_position_embeddings"], rope_theta=float(hf["rope_theta"]),
        rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]), head_dim=hf["head_dim"],
        indexer_heads=sa["indexer_num_heads"], indexer_head_dim=sa["indexer_head_dim"],
        indexer_topk=sa["topk"], index_chunk=sa.get("q_chunk_size", 512),
    )
    fields.update(overrides)  # caller wins on collisions
    return dataclasses.replace(dsa.KEYE_VL_2_30B_A3B, **fields)


def _olmo_hybrid_from_hf(hf: dict, **overrides):
    """`model_type` "olmo_hybrid" (allenai/Olmo-Hybrid-7B): gated-delta-rule
    linear-attention layers (the `linear_*` keys) and full-attention
    layers without a rotary (`layer_types`), every layer over a dense
    SwiGLU. What this decoder does not implement is refused by name;
    that the stack ends on a whole period is checked last."""
    from ray_tpu.models import olmo_hybrid as oh

    n = hf["num_hidden_layers"]
    types = list(hf.get("layer_types") or [])
    rope = hf.get("rope_parameters") or {}
    theta = rope.get("rope_theta", hf.get("rope_theta"))
    refused = {
        f"rope_theta {theta} (the full layers run without a rotary)": theta is not None,
        "layer_types other than linear_attention / full_attention":
            any(t not in (oh.LINEAR, oh.FULL) for t in types),
        "layer_types shorter than num_hidden_layers": len(types) < n,
        f"linear_num_key_heads {hf.get('linear_num_key_heads')} other than linear_num_value_heads "
        f"{hf.get('linear_num_value_heads')} (grouped keys are not implemented)":
            hf.get("linear_num_key_heads") != hf.get("linear_num_value_heads"),
        "key-value heads other than the query heads":
            hf.get("num_key_value_heads", hf["num_attention_heads"]) != hf["num_attention_heads"],
        "a sliding window": hf.get("sliding_window") is not None,
        "attention_bias": bool(hf.get("attention_bias")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
    }
    if any(refused.values()):
        raise ValueError("an olmo_hybrid config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_attention_heads"],
        d_ff=hf["intermediate_size"], max_seq=hf["max_position_embeddings"],
        rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_types=tuple(types), linear_heads=hf["linear_num_value_heads"],
        linear_key_dim=hf["linear_key_head_dim"], linear_value_dim=hf["linear_value_head_dim"],
        conv_kernel=hf["linear_conv_kernel_dim"],
        allow_neg_eigval=bool(hf.get("linear_allow_neg_eigval", False)),
    )
    fields.update(overrides)  # caller wins on collisions
    config = dataclasses.replace(oh.OLMO_HYBRID_7B, **fields)
    oh.logical_axes(config)  # raises where the stack does not end on a whole period
    return config


def _nemotron_h_from_hf(hf: dict, **overrides):
    """`model_type` "nemotron_h" (the causal tower of
    nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16): Mamba-2 mixers,
    relu^2 experts behind a sigmoid router and attention layers without a
    rotary, one sublayer a layer by `hybrid_override_pattern`. What this
    decoder does not implement is refused by name (the published model's
    second tower and its diffusion objective have no key to refuse: they
    are not built, models/nemotron_h.py)."""
    from ray_tpu.models import nemotron_h as nh

    pattern, n = hf["hybrid_override_pattern"], hf["num_hidden_layers"]
    refused = {
        "dense MLP layers (`-` in hybrid_override_pattern)": "-" in pattern[:n],
        "layer kinds other than M, E, * and -": bool(set(pattern) - set("ME*-")),
        "hybrid_override_pattern shorter than num_hidden_layers": len(pattern) < n,
        "a bias (attention_bias, mlp_bias, use_bias or mamba_proj_bias)": any(
            hf.get(k) for k in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias")),
        "a convolution without a bias": not hf.get("use_conv_bias", True),
        f"n_group {hf.get('n_group')} / topk_group {hf.get('topk_group')} (groups of experts)":
            hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1,
        "a sliding window": hf.get("sliding_window") is not None,
        f"mlp_hidden_act {hf.get('mlp_hidden_act')!r}": hf.get("mlp_hidden_act") != "relu2",
        f"mamba_hidden_act {hf.get('mamba_hidden_act')!r}": hf.get("mamba_hidden_act") != "silu",
        f"n_shared_experts {hf.get('n_shared_experts')}": hf.get("n_shared_experts") != 1,
        f"time_step_limit {hf.get('time_step_limit')} (a clamp on the step)":
            list(hf.get("time_step_limit") or [0, None]) not in ([0, None], [0.0, None]),
    }
    if any(refused.values()):
        raise ValueError("a nemotron_h config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"], d_ff=hf["moe_intermediate_size"],
        shared_d_ff=hf["moe_shared_expert_intermediate_size"],
        max_seq=hf["max_position_embeddings"], rms_eps=float(hf["layer_norm_epsilon"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["n_routed_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling=float(hf["routed_scaling_factor"]), pattern=pattern,
        mamba_heads=hf["mamba_num_heads"], mamba_head_dim=hf["mamba_head_dim"],
        ssm_groups=hf["n_groups"], ssm_state=hf["ssm_state_size"],
        conv_kernel=hf["conv_kernel"], chunk_size=hf["chunk_size"],
        time_step_min=hf["time_step_min"], time_step_max=hf["time_step_max"],
        time_step_floor=hf["time_step_floor"], published_layers=n,
    )
    fields.update(overrides)  # caller wins on collisions
    return dataclasses.replace(nh.NEMOTRON_TWOTOWER_30B_A3B, **fields)


def _solar_open2_from_hf(hf: dict, **overrides):
    """`model_type` "solar_open2" (upstage/Solar-Open2-250B): Kimi-Delta-
    Attention layers (`linear_attn_config`) and gated GQA layers without a
    rotary (`gqa_layers`), every layer over a sigmoid-routed expert layer
    with a shared expert. What this decoder does not implement is refused
    by name; that the stack ends on a whole period is checked last."""
    from ray_tpu.models import solar_open2 as so

    n = hf["num_hidden_layers"]
    lin = hf.get("linear_attn_config") or {}
    refused = {
        "use_rope (the GQA layers run without a rotary)": bool(hf.get("use_rope")),
        "kda_use_full_proj (one full matrix for the decay in place of the low-rank pair)":
            bool(hf.get("kda_use_full_proj")),
        "use_gqa_gate false (a GQA layer without its output gate)":
            not hf.get("use_gqa_gate", False),
        f"first_k_dense_replace {hf.get('first_k_dense_replace')} (a leading dense layer)":
            bool(hf.get("first_k_dense_replace")),
        f"linear_attn_config.num_kv_heads {lin.get('num_kv_heads')} (grouped keys under KDA)":
            lin.get("num_kv_heads") not in (None, lin.get("num_heads")),
        f"n_shared_experts {hf.get('n_shared_experts')}": hf.get("n_shared_experts") != 1,
        f"n_group {hf.get('n_group')} / topk_group {hf.get('topk_group')} (groups of experts)":
            hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1,
        "a sliding window": hf.get("sliding_window") is not None,
        "attention_bias": bool(hf.get("attention_bias")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
        "gqa_layers beyond num_hidden_layers' reach or none at all":
            not any(l < n for l in hf.get("gqa_layers") or ()),
    }
    if any(refused.values()):
        raise ValueError("a solar_open2 config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"], d_ff=hf["moe_intermediate_size"],
        shared_d_ff=hf["moe_intermediate_size"] * hf["n_shared_experts"],
        max_seq=hf["max_position_embeddings"], rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["n_routed_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling=float(hf["routed_scaling_factor"]),
        gqa_layers=tuple(hf["gqa_layers"]),
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"], kda_rank=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        kda_neg_eigval=bool(hf.get("kda_allow_neg_eigval", False)),  # fla's default: false
    )
    fields.update(overrides)  # caller wins on collisions
    config = dataclasses.replace(so.SOLAR_OPEN2_250B, **fields)
    so.logical_axes(config)  # raises where the stack does not end on a whole period
    return config


def _kimi_linear_from_hf(hf: dict, **overrides):
    """`model_type` "kimi_linear" (moonshotai/Kimi-Linear-48B-A3B-Instruct):
    Kimi-Delta-Attention layers (`linear_attn_config.kda_layers`) and
    latent-attention layers without a rotary and without a query latent
    (`full_attn_layers`, numbered from 1), leading dense layers, then
    sigmoid-routed experts with a shared one. What this decoder does not
    implement is refused by name."""
    from ray_tpu.models import kimi_linear as kl

    n = hf["num_hidden_layers"]
    lin = hf.get("linear_attn_config") or {}
    numbered = set(lin.get("kda_layers") or ()) | set(lin.get("full_attn_layers") or ())
    refused = {
        "mla_use_nope false (a rotary on the latent-attention layers' 64 channels)":
            not hf.get("mla_use_nope", False),
        f"rope_scaling {hf.get('rope_scaling')!r}": hf.get("rope_scaling") is not None,
        f"q_lora_rank {hf.get('q_lora_rank')} (a query with a latent under this stack)":
            hf.get("q_lora_rank") is not None,
        f"num_expert_group {hf.get('num_expert_group')} / topk_group {hf.get('topk_group')} "
        "(groups of experts)":
            hf.get("num_expert_group", 1) != 1 or hf.get("topk_group", 1) != 1,
        f"num_nextn_predict_layers {hf.get('num_nextn_predict_layers')} (multi-token prediction)":
            bool(hf.get("num_nextn_predict_layers")),
        f"moe_layer_freq {hf.get('moe_layer_freq')} (dense layers among the expert layers)":
            hf.get("moe_layer_freq", 1) != 1,
        f"moe_router_activation_func {hf.get('moe_router_activation_func')!r}":
            hf.get("moe_router_activation_func") != "sigmoid",
        f"num_shared_experts {hf.get('num_shared_experts')}": hf.get("num_shared_experts") != 1,
        f"num_key_value_heads {hf.get('num_key_value_heads')} (grouped keys under MLA)":
            hf.get("num_key_value_heads", hf["num_attention_heads"]) != hf["num_attention_heads"],
        f"linear_attn_config.num_kv_heads {lin.get('num_kv_heads')} (grouped keys under KDA)":
            lin.get("num_kv_heads") not in (None, lin.get("num_heads")),
        "kda_layers and full_attn_layers that do not number every layer once":
            numbered != set(range(1, max(numbered, default=0) + 1))
            or len(numbered) != len(lin.get("kda_layers") or ()) + len(lin.get("full_attn_layers") or ())
            or n > len(numbered),
        "no expert layer (first_k_dense_replace reaches the last layer)":
            hf.get("first_k_dense_replace", 0) >= n,
        "attention_bias": bool(hf.get("attention_bias")),
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act", "silu") != "silu",
    }
    if any(refused.values()):
        raise ValueError("a kimi_linear config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_attention_heads"],
        d_ff=hf["moe_intermediate_size"],
        shared_d_ff=hf["moe_intermediate_size"] * hf["num_shared_experts"],
        dense_d_ff=hf["intermediate_size"], first_dense_layers=hf.get("first_k_dense_replace", 0),
        max_seq=hf.get("model_max_length", hf.get("max_position_embeddings", 8192)),
        rope_theta=float(hf.get("rope_theta", 10000.0)), rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        n_experts=hf["num_experts"], top_k=hf["num_experts_per_token"],
        norm_topk_prob=bool(hf["moe_renormalize"]),
        routed_scaling=float(hf["routed_scaling_factor"]),
        mla_layers=tuple(lin["full_attn_layers"]),
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"], kda_rank=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        kda_neg_eigval=bool(hf.get("kda_allow_neg_eigval", False)),
        kv_lora_rank=hf["kv_lora_rank"], qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
    )
    fields.update(overrides)  # caller wins on collisions
    config = dataclasses.replace(kl.KIMI_LINEAR_48B_A3B, **fields)
    kl.logical_axes(config)  # raises where the layers cannot be laid out
    return config


def _granite_hybrid_from_hf(hf: dict, **overrides):
    """`model_type` "granitemoehybrid" (ibm-granite/granite-4.0-h-micro):
    Mamba-2 mixers (B and C in `mamba_n_groups` groups) and GQA layers
    without a rotary by `layer_types`, each over a dense SwiGLU
    (`shared_intermediate_size`), four muP multipliers, a tied table. What
    models/granite_hybrid.py does not implement is refused by name: the
    family's members with routed experts among it."""
    from ray_tpu.models import granite_hybrid as gh

    heads, groups = hf["mamba_n_heads"], hf["mamba_n_groups"]
    refused = {
        f"num_local_experts {hf.get('num_local_experts')} (routed experts)":
            bool(hf.get("num_local_experts")) or bool(hf.get("num_experts_per_tok")),
        f"position_embedding_type {hf.get('position_embedding_type')!r} (a rotary)":
            hf.get("position_embedding_type") != "nope",
        f"mamba_n_groups {groups}, which does not divide the {heads} heads":
            groups < 1 or heads % groups != 0,
        "a bias (attention_bias or mamba_proj_bias)":
            bool(hf.get("attention_bias")) or bool(hf.get("mamba_proj_bias")),
        "a convolution without a bias": not hf.get("mamba_conv_bias", True),
        f"layer types other than mamba and attention ({sorted(set(hf['layer_types']))})":
            bool(set(hf["layer_types"]) - set(gh.KINDS)),
        "layer_types shorter than num_hidden_layers":
            len(hf["layer_types"]) < hf["num_hidden_layers"],
        f"hidden_act {hf.get('hidden_act')!r}": hf.get("hidden_act") != "silu",
        f"normalization_function {hf.get('normalization_function')!r}":
            hf.get("normalization_function", "rmsnorm") != "rmsnorm",
        "rope_scaling": hf.get("rope_scaling") is not None,
        f"mamba_expand {hf.get('mamba_expand')}, which is not heads x head width / hidden":
            hf["mamba_expand"] * hf["hidden_size"] != heads * hf["mamba_d_head"],
    }
    if any(refused.values()):
        raise ValueError("a granitemoehybrid config with "
                         + ", ".join(k for k, v in refused.items() if v) + " is not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["shared_intermediate_size"], max_seq=hf["max_position_embeddings"],
        rope_theta=float(hf.get("rope_theta", 10000.0)), rms_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", True)),
        published_types=tuple(hf["layer_types"]), published_layers=hf["num_hidden_layers"],
        mamba_heads=heads, mamba_head_dim=hf["mamba_d_head"], ssm_groups=groups,
        ssm_state=hf["mamba_d_state"], conv_kernel=hf["mamba_d_conv"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        attention_multiplier=float(hf["attention_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
    )
    fields.update(overrides)  # caller wins on collisions
    return dataclasses.replace(gh.GRANITE_4_H_MICRO, **fields)


# `model_type` -> the family's mapping (any other: the llama / mixtral / OLMoE one below)
_FROM_HF = {
    "zaya": _zaya_from_hf, "glm4_moe_lite": _glm_lite_from_hf, "laguna": _laguna_from_hf,
    "mellum": _mellum_from_hf, "sdar_moe": _sdar_from_hf, "KeyeVL2": _keye_from_hf,
    "olmo_hybrid": _olmo_hybrid_from_hf, "nemotron_h": _nemotron_h_from_hf,
    "solar_open2": _solar_open2_from_hf, "kimi_linear": _kimi_linear_from_hf,
    "granitemoehybrid": _granite_hybrid_from_hf,
}


def config_from_hf(hf: dict, **overrides):
    """Map a HF `config.json` dict to a LlamaConfig/MoEConfig/ZayaConfig/
    GlmLiteConfig/LagunaConfig/KeyeConfig/OlmoHybridConfig/NemotronHConfig.

    Only architecture hyperparameters travel; framework knobs
    (dtype/remat/attention_impl) keep their TPU defaults unless
    overridden. Raises on architectures outside the llama/mixtral
    families rather than mis-mapping them. OLMoE (`OlmoeForCausalLM`,
    or `model_type` "olmoe" in a dict with no architectures field):
    `intermediate_size` is the width of one expert, q and k are
    normalised, and the top-k weights are renormalised only if
    `norm_topk_prob` says so. ZAYA1 (`model_type` "zaya"): see
    `_zaya_from_hf`. GLM-4.7-Flash (`model_type` "glm4_moe_lite", whose
    heads are not hidden_size / heads wide): see `_glm_lite_from_hf`.
    Laguna (`model_type` "laguna": `rope_parameters` by layer type, yarn
    among them, an explicit `head_dim`, head counts by layer): see
    `_laguna_from_hf`. Mellum2 (`model_type` "mellum": the same stack at one
    head count, a norm a head, no gate): see `_mellum_from_hf`. SDAR
    (`model_type` "sdar_moe": that stack's full layers alone, trained by
    block diffusion): see `_sdar_from_hf`. The language
    model of Keye-VL-2.0 (`model_type`
    "KeyeVL2"): see `_keye_from_hf`. Olmo-Hybrid (`model_type`
    "olmo_hybrid": linear-attention layers beside full ones, no rotary):
    see `_olmo_hybrid_from_hf`. Nemotron-H (`model_type` "nemotron_h": Mamba-2
    mixers, relu^2 experts, attention without a rotary): see
    `_nemotron_h_from_hf`. Solar-Open2 (`model_type` "solar_open2": KDA layers
    beside gated GQA ones over sigmoid-routed experts): see
    `_solar_open2_from_hf`. Kimi-Linear (`model_type` "kimi_linear": KDA layers
    beside latent-attention layers without a rotary, a dense layer, experts):
    see `_kimi_linear_from_hf`. Granite 4.0-H (`model_type` "granitemoehybrid":
    Mamba-2 mixers in one group and GQA without a rotary, each over a dense
    SwiGLU, muP multipliers, a tied table): see `_granite_hybrid_from_hf`. For
    every OTHER family a
    `rope_scaling` and an explicit `head_dim` that is not hidden_size /
    heads stay refused.
    """
    family = _FROM_HF.get(hf.get("model_type"))
    if family is not None:
        return family(hf, **overrides)
    archs = set(hf.get("architectures", ()))
    is_olmoe = "OlmoeForCausalLM" in archs or (not archs and hf.get("model_type") == "olmoe")
    # the num_local_experts heuristic only applies to config dicts with NO
    # architectures field: PhiMoE/GPT-OSS-style configs also carry it and
    # must be rejected by the whitelist, not mapped onto Mixtral
    is_moe = is_olmoe or bool(archs & _HF_MOE_ARCHS) or (
        not archs and "num_local_experts" in hf
    )
    if archs and not is_moe and not (archs & _HF_LLAMA_ARCHS):
        raise ValueError(
            f"unsupported architectures {sorted(archs)}; llama-family "
            f"({sorted(_HF_LLAMA_ARCHS)}) and mixtral-family "
            f"({sorted(_HF_MOE_ARCHS)}) map onto this framework's decoders"
        )
    scaling = hf.get("rope_scaling")
    if scaling and scaling.get("rope_type", scaling.get("type")) != "default":
        # llama-3.1-style frequency rescaling changes every position's
        # rotation; mapping rope_theta alone would diverge silently
        raise ValueError(
            f"rope_scaling={scaling!r} is not supported; only default RoPE "
            "maps onto this decoder"
        )
    derived_hd = hf["hidden_size"] // hf["num_attention_heads"]
    if hf.get("head_dim") not in (None, derived_hd):
        raise ValueError(
            f"explicit head_dim={hf['head_dim']} != hidden_size/"
            f"num_attention_heads={derived_hd}; this decoder derives "
            "head_dim and would mis-shape the checkpoint"
        )
    common = dict(
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        d_ff=hf["intermediate_size"],
        max_seq=hf.get("max_position_embeddings", 8192),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )
    if is_olmoe:
        if hf.get("clip_qkv") is not None or hf.get("attention_bias"):
            raise ValueError("clip_qkv and attention biases are not supported")
        common.update(
            n_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)), qk_norm=True,
            router_aux_coeff=float(hf.get("router_aux_loss_coef", 0.01)),
            router_z_coeff=0.001,  # arXiv:2409.02060; the HF config has no key for it
        )
    elif is_moe:
        common["n_experts"] = hf["num_local_experts"]
        common["top_k"] = hf.get("num_experts_per_tok", 2)
    if is_moe:
        common.update(overrides)  # caller wins on collisions
        return moe.MoEConfig(**common)
    common.update(overrides)
    return llama.LlamaConfig(**common)
