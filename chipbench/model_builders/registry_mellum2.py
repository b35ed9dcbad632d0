"""Mellum2-12B-A2.5B of the program's registry as ONE CHIP'S SHARE of a
stated deployment: depth cut (`num_hidden_layers`: the published lists
`layer_types` and `mlp_layer_types` stay whole in the file and their
first `num_hidden_layers` entries are run), `num_experts` of the
published experts held (from `deployment.first_expert_held`),
`vocab_size` rows of the embedding and columns of the head held, and
nothing else changed. Every width in the configuration file, the window,
and each number of both rotary groups must equal the registry entry's,
and the registry entry must be at the file's `published` counts, or the
run fails.

The weights are what `llama.init_params` gives a key, the router's
SELECTION BIASES (b of `top-k(p + b)`, which take no gradient and which
no step moves) zero among them: one table of the model,
`params["layers"]["router_bias"]` [layers, experts], in layer order.

`balanced_bias` makes the table that the cell's runner puts in that
parameter's place before the first step: the bias under which every
expert of a layer sees as many of the run's own tokens as the next, the
state the balancing of arXiv:2408.15664 holds a deployment in: this chip
then holds its share (a quarter or an eighth) of every layer's pairs. It
is the rule of model_builders/registry_laguna.py and registry_keye.py
(GLM's and ZAYA1's before them), ONE fixed rule with no option, at THIS
router's constants (each builder reads its own module's): the sign rule
(b_e up by a step where expert e saw fewer pairs than the mean, down
where more), PASSES forward passes of the program's own loss function
over fresh batches of the run's traffic, all layers at once, the step
falling geometrically from STEP_FIRST to STEP_LAST; the last AVERAGED
passes' tables are averaged. Its one program takes the weights, the
table and the batch as ARGUMENTS, so it is compiled once for all seeds."""

from __future__ import annotations

import dataclasses

# The rule's constants, fixed here and read from no file. A fresh
# router's logits have unit variance, so a token's 64 probabilities are
# exp(N(0, 1)) / 105.5: their mean 1/64 = 0.0156, the eighth largest about
# 0.0299 (1.15 deviations up) and the eighth and ninth 2.3e-3 apart
# (0.076 deviations: 1 / (64 x the normal density there)). The steps are
# Laguna's, Keye's and GLM's multiples of that gap, 2.5 and 0.075, and the
# table can travel 0.08 in its 48 passes, as far as the chosen
# probabilities spread.
PASSES, AVERAGED = 48, 16
STEP_FIRST, STEP_LAST = 5.7e-3, 1.7e-4
FIRST_BATCH = 1 << 20  # the passes' batches: far from the steps' own (0, 1, 2, ...)

# configuration-file key -> LagunaConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "head_dim": "head_dim", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "intermediate_size": "dense_d_ff",
          "moe_intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
          "norm_topk_prob": "norm_topk_prob", "sliding_window": "sliding_window",
          "rms_norm_eps": "rms_eps", "max_position_embeddings": "max_seq",
          "tie_word_embeddings": "tie_embeddings"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "num_experts": "n_experts", "vocab_size": "vocab_size"}
# a rotary group's key -> Rotary attribute
ROTARY = {"rope_theta": "theta", "rope_type": "rope_type", "factor": "factor",
          "original_max_position_embeddings": "original_max", "beta_fast": "beta_fast",
          "beta_slow": "beta_slow", "attention_factor": "attention_factor"}


def build(config: dict, **overrides):
    """-> (LagunaConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    n = config["published"]["num_hidden_layers"]
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"],
                 "layer_types": tuple(config["layer_types"]),
                 "dense_layers": tuple(l for l, t in enumerate(config["mlp_layer_types"])
                                       if t == "dense")}
    program_side = {**{k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()},
                    "layer_types": full.layer_types[:n],
                    "dense_layers": tuple(range(full.first_dense_layers))}
    for kind, rotary in (("full_attention", full.rope_full), ("sliding_attention", full.rope_sliding)):
        for key, value in config["rope_parameters"][kind].items():
            file_side[f"{kind}.{key}"] = value
            program_side[f"{kind}.{key}"] = getattr(rotary, ROTARY[key])
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    unrun = {k: config[k] for k in ("attention_bias",) if config[k]}
    if (wrong or unrun or full.router_score != "softmax" or full.attn_gate != "none"
            or not full.qk_head_norm or full.heads_per_layer or full.shared_d_ff
            or full.routed_scaling != 1.0 or full.rope_full.partial != 1.0
            or not config["use_sliding_window"] or config["hidden_act"] != "silu"):
        raise RuntimeError(f"{config['registry_model']} is not at the file's sizes "
                           f"(file, program): {wrong}; not run: {unrun}")
    cfg = dataclasses.replace(
        full, n_layers=config["num_hidden_layers"], vocab_size=config["vocab_size"],
        experts_held=config["num_experts"],
        first_expert_held=config["deployment"]["first_expert_held"], **overrides)

    def init(key):
        return llama.init_params(cfg, key)

    return cfg, init, llama.logical_axes(cfg)


def balanced_bias(cfg, params, make):
    """-> the selection biases, float32 [layers, experts], under which
    `params` (the share `cfg`, as `build` gives them) route equal
    numbers of the pairs of `make(i)` (the run's batches) to every
    expert of a layer."""
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
