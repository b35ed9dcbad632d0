"""A llama-family model of the program's registry with depth cut and
nothing else changed: every width in the configuration file must equal
the registry entry's, or the run fails (the check of chip_smoke.py's
smoke_model, copied)."""

from __future__ import annotations

import dataclasses

# configuration-file key -> LlamaConfig attribute
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
          "head_dim": "head_dim", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
          "max_position_embeddings": "max_seq"}


def build(config: dict, **overrides):
    """-> (LlamaConfig, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    if full.n_layers != config["published"]["num_hidden_layers"]:
        raise RuntimeError(
            f"{config['registry_model']} has {full.n_layers} layers in the registry, "
            f"{config['published']['num_hidden_layers']} published")
    cfg = dataclasses.replace(full, n_layers=config["num_hidden_layers"], **overrides)
    wrong = {k: (config[k], getattr(cfg, a)) for k, a in WIDTHS.items()
             if k in config and config[k] != getattr(cfg, a)}
    if wrong or cfg.tie_embeddings != config["tie_word_embeddings"]:
        raise RuntimeError(
            f"{config['registry_model']} is not at the file's sizes (file, program): {wrong}")
    return cfg, (lambda key: llama.init_params(cfg, key)), llama.logical_axes(cfg)
