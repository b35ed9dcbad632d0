"""Kimi-Linear-48B-A3B of the program's registry as ONE CHIP'S SHARE of a
stated deployment: depth cut (`num_hidden_layers` layers from layer 1;
`kda_layers` and `full_attn_layers` stay whole in the file), `num_experts`
of the published experts held (from `deployment.first_expert_held`),
`vocab_size` rows of the embedding and columns of the head held, both
mixers' heads WHOLE, and nothing else changed. Every width in the
configuration file must equal the registry entry's, and the registry entry
must be at the file's `published` counts, or the run fails.

The weights are what `llama.init_params` gives a key, the router's
SELECTION BIASES (b of `top-k(s + b)`, which take no gradient and which no
step moves) zero among them: `params["layers"]["router_bias"]` [expert
layers, experts].

`balanced_bias` makes the table that the cell's runner puts in that
parameter's place before the first step: the bias under which every
expert of a layer sees as many of the run's own tokens as the next, the
state the balancing of arXiv:2408.15664 holds a deployment in: this chip
then holds a thirty-second of every layer's pairs. It is the rule of
model_builders/registry_glm_lite.py and registry_solar_open2.py (a sigmoid
router's), ONE fixed rule with no option, at THIS router's constants; it
is not imported from those builders because each reads its own module's
constants: the sign rule (b_e up by a step where expert e saw fewer pairs
than the mean, down where more), PASSES forward passes of the program's
own loss function over fresh batches of the run's traffic, all layers at
once, the step falling geometrically from STEP_FIRST to STEP_LAST; the
last AVERAGED passes' tables are averaged. Its one program takes the
weights, the table and the batch as ARGUMENTS, so it is compiled once for
all seeds."""

from __future__ import annotations

import dataclasses

# The rule's constants, fixed here and read from no file. A fresh router's
# scores are sigmoids of logits of unit variance; a token's eighth largest of
# 256 lies 1.84 deviations up (a score of 0.862) and its eighth and ninth
# 0.053 deviations = 0.0063 of a score apart (1 / (256 x the normal density
# there) x s (1 - s)). The steps are GLM's multiples of that gap, 2.5 and 0.075.
PASSES, AVERAGED = 48, 16
STEP_FIRST, STEP_LAST = 0.0157, 4.7e-4
FIRST_BATCH = 1 << 20  # the passes' batches: far from the steps' own (0, 1, 2, ...)

# configuration-file key -> KimiLinearConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "dense_d_ff",
          "moe_intermediate_size": "d_ff", "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
          "v_head_dim": "v_head_dim", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "num_experts_per_token": "top_k",
          "moe_renormalize": "norm_topk_prob", "routed_scaling_factor": "routed_scaling",
          "rms_norm_eps": "rms_eps", "model_max_length": "max_seq",
          "tie_word_embeddings": "tie_embeddings", "first_k_dense_replace": "first_dense_layers"}
# the same inside `linear_attn_config`
LINEAR_WIDTHS = {"head_dim": "kda_head_dim", "num_heads": "kda_heads",
                 "short_conv_kernel_size": "conv_kernel"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "num_experts": "n_experts", "vocab_size": "vocab_size"}
# what the program runs in one form only: the file must say that form
FORMS = {"mla_use_nope": True, "q_lora_rank": None, "rope_scaling": None, "num_expert_group": 1,
         "topk_group": 1, "num_nextn_predict_layers": 0, "moe_layer_freq": 1,
         "num_shared_experts": 1, "moe_router_activation_func": "sigmoid", "hidden_act": "silu"}


def build(config: dict, **overrides):
    """-> (KimiLinearConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    lin, published = config["linear_attn_config"], config["published"]
    file_side = {**{k: config[k] for k in WIDTHS}, **{k: published[k] for k in COUNTS},
                 **{f"linear_attn_config.{k}": lin[k] for k in LINEAR_WIDTHS},
                 "full_attn_layers": tuple(lin["full_attn_layers"])}
    program_side = {**{k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()},
                    **{f"linear_attn_config.{k}": getattr(full, a)
                       for k, a in LINEAR_WIDTHS.items()},
                    "full_attn_layers": full.mla_layers}
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    unrun = {k: config[k] for k, form in FORMS.items() if config[k] != form}
    every = sorted(lin["kda_layers"] + lin["full_attn_layers"])
    if (wrong or unrun or full.router_score != "sigmoid" or full.kda_rank != lin["head_dim"]
            or full.kda_neg_eigval or full.mla_rope or full.q_lora_rank
            or full.shared_d_ff != config["moe_intermediate_size"]
            or every != list(range(1, published["num_hidden_layers"] + 1))):
        raise RuntimeError(f"{config['registry_model']} is not at the file's sizes "
                           f"(file, program): {wrong}; not run: {unrun}; layers numbered {every}")
    cfg = dataclasses.replace(
        full, n_layers=config["num_hidden_layers"], vocab_size=config["vocab_size"],
        experts_held=config["num_experts"],
        first_expert_held=config["deployment"]["first_expert_held"], **overrides)

    def init(key):
        return llama.init_params(cfg, key)

    return cfg, init, llama.logical_axes(cfg)


def balanced_bias(cfg, params, make):
    """-> the selection biases, float32 [expert layers, experts], under
    which `params` (the share `cfg`, as `build` gives them) route equal
    numbers of the pairs of `make(i)` (the run's batches) to every expert
    of a layer."""
    import jax
    import numpy as np

    from ray_tpu.models import llama

    @jax.jit
    def counts(params, bias, batch):
        layers = {**params["layers"], "router_bias": bias.astype(cfg.param_dtype)}
        stats = llama.loss_and_weight_fn({**params, "layers": layers}, batch, cfg)[2]
        return stats["tokens_per_expert"]

    bias = np.zeros(params["layers"]["router_bias"].shape, np.float32)
    kept = []
    for i in range(PASSES):
        seen = np.asarray(counts(params, bias, make(FIRST_BATCH + i)), np.float64)
        step = STEP_FIRST * (STEP_LAST / STEP_FIRST) ** (i / (PASSES - 1))
        bias = bias + np.float32(step) * np.sign(seen.mean(-1, keepdims=True) - seen)
        kept.append(bias)
    return np.mean(kept[-AVERAGED:], axis=0, dtype=np.float32)
