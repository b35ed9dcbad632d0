"""Olmo-Hybrid-7B of the program's registry as ONE STAGE of a stated
deployment: depth cut to whole periods of layer kinds (the published
list `layer_types` stays whole in the file and its first
`num_hidden_layers` entries are run), `vocab_size` rows of the embedding
and columns of the head held, and nothing else changed. Every width in
the configuration file must equal the registry entry's, and the registry
entry must be at the file's `published` counts, or the run fails. The
weights are what `llama.init_params` gives a key."""

from __future__ import annotations

import dataclasses

# configuration-file key -> OlmoHybridConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
          "linear_num_key_heads": "linear_heads", "linear_num_value_heads": "linear_heads",
          "linear_key_head_dim": "linear_key_dim", "linear_value_head_dim": "linear_value_dim",
          "linear_conv_kernel_dim": "conv_kernel", "linear_allow_neg_eigval": "allow_neg_eigval",
          "rms_norm_eps": "rms_eps", "max_position_embeddings": "max_seq",
          "tie_word_embeddings": "tie_embeddings"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size"}


def build(config: dict, **overrides):
    """-> (OlmoHybridConfig of the stage, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"],
                 "layer_types": tuple(config["layer_types"])}
    program_side = {**{k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()},
                    "layer_types": full.layer_types}
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    if wrong or config["rope_parameters"]["rope_theta"] is not None or config["attention_bias"]:
        raise RuntimeError(
            f"{config['registry_model']} is not at the file's sizes (file, program): {wrong}")
    cfg = dataclasses.replace(full, n_layers=config["num_hidden_layers"],
                              vocab_size=config["vocab_size"], **overrides)

    def init(key):
        return llama.init_params(cfg, key)

    return cfg, init, llama.logical_axes(cfg)
