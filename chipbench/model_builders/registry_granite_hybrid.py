"""granite-4.0-h-micro of the program's registry as ONE CHIP'S SHARE of a
stated deployment: depth cut (the first `num_hidden_layers` entries of
`layer_types`, which stays whole in the file), `vocab_size` rows of the
tied table held, and nothing else changed. Every width and every
multiplier in the configuration file must equal the registry entry's,
and the registry entry must be at the file's `published` counts, or the
run fails. A dense model: there is no router, so no selection bias is
balanced and no state stands between the init and the first step.

The weights are what `llama.init_params` gives a key."""

from __future__ import annotations

import dataclasses

# configuration-file key -> GraniteHybridConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "shared_intermediate_size": "d_ff",
          "rms_norm_eps": "rms_eps", "max_position_embeddings": "max_seq",
          "tie_word_embeddings": "tie_embeddings", "mamba_n_heads": "mamba_heads",
          "mamba_d_head": "mamba_head_dim", "mamba_n_groups": "ssm_groups",
          "mamba_d_state": "ssm_state", "mamba_d_conv": "conv_kernel",
          "embedding_multiplier": "embedding_multiplier",
          "residual_multiplier": "residual_multiplier",
          "attention_multiplier": "attention_multiplier", "logits_scaling": "logits_scaling",
          "rope_theta": "rope_theta"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size"}
# what the program runs in one form only: the file must say that form
FORMS = {"hidden_act": "silu", "mamba_conv_bias": True, "mamba_proj_bias": False,
         "attention_bias": False, "num_local_experts": 0, "num_experts_per_tok": 0,
         "position_embedding_type": "nope", "normalization_function": "rmsnorm",
         "rope_scaling": None, "mamba_expand": 2}


def build(config: dict, **overrides):
    """-> (GraniteHybridConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"]}
    program_side = {k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()}
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    unrun = {k: config[k] for k, form in FORMS.items() if config[k] != form}
    if (wrong or unrun or tuple(config["layer_types"]) != full.published_types
            or full.published_layers != config["published"]["num_hidden_layers"]
            or full.chunk_size != config["assumed_sizes"]["chunk_size"]):
        raise RuntimeError(f"{config['registry_model']} is not at the file's sizes "
                           f"(file, program): {wrong}; not run: {unrun}")
    cfg = dataclasses.replace(
        full, n_layers=config["num_hidden_layers"], vocab_size=config["vocab_size"],
        remat_policy=config["train"]["remat_policy"], **overrides)

    def init(key):
        return llama.init_params(cfg, key)

    return cfg, init, llama.logical_axes(cfg)
