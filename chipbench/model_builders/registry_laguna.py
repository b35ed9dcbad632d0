"""Laguna-S-2.1 of the program's registry as ONE CHIP'S SHARE of a stated
deployment: depth cut (`num_hidden_layers` counts the leading dense
layer and the expert layers: the published lists `layer_types`,
`mlp_layer_types`, `gating_types` and `num_attention_heads_per_layer`
stay whole in the file and their first `num_hidden_layers` entries are
run), `num_experts` of the published experts held (from
`deployment.first_expert_held`), `vocab_size` rows of the embedding and
columns of the head held, and nothing else changed. Every width in the
configuration file must equal the registry entry's, and the registry
entry must be at the file's `published` counts, or the run fails.

The weights are what `llama.init_params` gives a key, the router's
SELECTION BIASES (b of `top-k(p + b)`, which take no gradient and which
no step moves) zero among them. They are one table of the model,
`params["layers"]["router_bias"]` [expert layers, experts], in layer
order.

`balanced_bias` makes the table that the cell's runner puts in that
parameter's place before the first step: the bias under which every
expert of a block sees as many of the run's own tokens as the next, the
state the balancing of arXiv:2408.15664 holds a deployment in: this chip
then holds 1/32 of every block's pairs. It is
model_builders/registry_glm_lite.py's rule (ZAYA1's before it) carried
to ten of 256 softmax probabilities, ONE fixed rule with no option: the
sign rule (b_e up by a step where expert e saw fewer pairs than the
mean, down where more), PASSES forward passes of the program's own loss
function over fresh batches of the run's traffic, all blocks at once,
the step falling geometrically from STEP_FIRST to STEP_LAST; the last
AVERAGED passes' tables are averaged, which takes out the rule's own
oscillation. Its one program takes the weights, the table and the batch
as ARGUMENTS, so it is compiled once for all seeds."""

from __future__ import annotations

import dataclasses

# The rule's constants, fixed here and read from no file. A fresh
# router's logits have unit variance, so a token's 256 probabilities are
# exp(N(0, 1)) / 422: their mean 1/256 = 0.0039, the tenth largest about
# 0.0138 (1.76 deviations up) and the tenth and eleventh 6.4e-4 apart
# (0.046 deviations: 1 / (256 x the normal density there)). GLM's steps
# were 2.5 and 0.075 times ITS gap between the last chosen and the first
# not chosen (0.05 and 0.0015 against 0.02); the same multiples of this
# gap, and a table that can travel 0.02 in its 48 passes, as far as the
# chosen probabilities spread.
PASSES, AVERAGED = 48, 16
STEP_FIRST, STEP_LAST = 1.6e-3, 5e-5
FIRST_BATCH = 1 << 20  # the passes' batches: far from the steps' own (0, 1, 2, ...)

# configuration-file key -> LagunaConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "head_dim": "head_dim", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "intermediate_size": "dense_d_ff",
          "moe_intermediate_size": "d_ff", "shared_expert_intermediate_size": "shared_d_ff",
          "num_experts_per_tok": "top_k", "norm_topk_prob": "norm_topk_prob",
          "moe_routed_scaling_factor": "routed_scaling", "sliding_window": "sliding_window",
          "gating": "attn_gate", "rms_norm_eps": "rms_eps",
          "max_position_embeddings": "max_seq", "tie_word_embeddings": "tie_embeddings"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "num_experts": "n_experts", "vocab_size": "vocab_size"}
# a rotary group's key -> Rotary attribute
ROTARY = {"rope_theta": "theta", "rope_type": "rope_type", "partial_rotary_factor": "partial",
          "factor": "factor", "original_max_position_embeddings": "original_max",
          "beta_fast": "beta_fast", "beta_slow": "beta_slow", "attention_factor": "attention_factor"}


def build(config: dict, **overrides):
    """-> (LagunaConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    n = config["published"]["num_hidden_layers"]
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"],
                 "layer_types": tuple(config["layer_types"]),
                 "heads_per_layer": tuple(config["num_attention_heads_per_layer"]),
                 "dense_layers": tuple(l for l, t in enumerate(config["mlp_layer_types"])
                                       if t == "dense")}
    program_side = {**{k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()},
                    "layer_types": full.layer_types[:n], "heads_per_layer": full.heads_per_layer[:n],
                    "dense_layers": tuple(range(full.first_dense_layers))}
    for kind, rotary in (("full_attention", full.rope_full), ("sliding_attention", full.rope_sliding)):
        for key, value in config["rope_parameters"][kind].items():
            file_side[f"{kind}.{key}"] = value
            program_side[f"{kind}.{key}"] = getattr(rotary, ROTARY[key])
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    if (wrong or full.router_score != "softmax" or config["moe_router_logit_softcapping"]
            or config["moe_apply_router_weight_on_input"]):
        raise RuntimeError(
            f"{config['registry_model']} is not at the file's sizes (file, program): {wrong}")
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
    numbers of the pairs of `make(i)` (the run's batches) to every
    expert of a block."""
    import jax
    import numpy as np

    from ray_tpu.models import llama

    @jax.jit
    def counts(params, bias, batch):
        layers = {**params["layers"], "router_bias": bias.astype(cfg.param_dtype)}
        stats = llama.loss_and_weight_fn({**params, "layers": layers}, batch, cfg)[2]
        return stats["tokens_per_expert"]

    bias = np.zeros((cfg.n_expert_layers, cfg.n_experts), np.float32)
    kept = []
    for i in range(PASSES):
        seen = np.asarray(counts(params, bias, make(FIRST_BATCH + i)), np.float64)
        step = STEP_FIRST * (STEP_LAST / STEP_FIRST) ** (i / (PASSES - 1))
        bias = bias + np.float32(step) * np.sign(seen.mean(-1, keepdims=True) - seen)
        kept.append(bias)
    return np.mean(kept[-AVERAGED:], axis=0, dtype=np.float32)
