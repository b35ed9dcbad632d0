"""GLM-4.7-Flash of the program's registry as ONE CHIP'S SHARE of a stated
deployment: depth cut (`num_hidden_layers` counts the leading dense
layer and the expert layers; the multi-token-prediction block stands
beside them), `n_routed_experts` of the published experts held (from
`deployment.first_expert_held`), `vocab_size` rows of the embedding and
columns of the head held, and nothing else changed. Every width in the
configuration file must equal the registry entry's, and the registry
entry must be at the file's `published` counts, or the run fails.

The weights are what `llama.init_params` gives a key, the router's
SELECTION BIASES (b of `top-k(s + b)`, which take no gradient and which
no step moves: the update rule for them is a training recipe's and is
not in the program) zero among them. They are one table of the model,
`params["layers"]["router_bias"]` [expert layers + 1, experts], the
multi-token-prediction block's row last; `build` takes the configuration
and hands out an init that takes a key, and nothing of the traffic
reaches it.

`balanced_bias` makes the table that the cell's runner puts in that
parameter's place before the first step: the bias under which every
expert of a block (the MTP block's too) sees as many of the run's own
tokens as the next, which is the state the balancing of
arXiv:2408.15664 holds a deployment in: this chip then holds an eighth
of every block's pairs and routes 87.5% elsewhere. It is
model_builders/registry_zaya.py's rule carried to four experts a token,
ONE fixed rule with no option: the sign rule (b_e up by a step where
expert e saw fewer pairs than the mean, down where more), PASSES forward
passes of the program's own loss function over fresh batches of the
run's traffic, all blocks at once, the step falling geometrically from
STEP_FIRST to STEP_LAST; the last AVERAGED passes' tables are averaged,
which takes out the rule's own oscillation. Its one program takes the
weights, the table and the batch as ARGUMENTS, so it is compiled once
for all seeds."""

from __future__ import annotations

import dataclasses

# The rule's constants, fixed here and read from no file. A fresh
# router's scores are sigmoids of logits of unit variance: a token's 64
# scores lie 0.2 apart by their standard deviation and its fourth and
# fifth about 0.02, where ZAYA1's probabilities lay within a few
# hundredths of 1/16: the steps are that rule's, five times as long
# first and last.
PASSES, AVERAGED = 48, 16
STEP_FIRST, STEP_LAST = 0.05, 0.0015
FIRST_BATCH = 1 << 20  # the passes' batches: far from the steps' own (0, 1, 2, ...)

# configuration-file key -> GlmLiteConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "moe_intermediate_size": "d_ff",
          "intermediate_size": "dense_d_ff", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "q_lora_rank": "q_lora_rank",
          "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
          "num_experts_per_tok": "top_k", "routed_scaling_factor": "routed_scaling",
          "norm_topk_prob": "norm_topk_prob", "first_k_dense_replace": "first_dense_layers",
          "num_nextn_predict_layers": "mtp_layers", "rms_norm_eps": "rms_eps",
          "rope_theta": "rope_theta", "max_position_embeddings": "max_seq",
          "tie_word_embeddings": "tie_embeddings"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "n_routed_experts": "n_experts",
          "vocab_size": "vocab_size"}


def build(config: dict, **overrides):
    """-> (GlmLiteConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"],
                 "shared_width": config["n_shared_experts"] * config["moe_intermediate_size"]}
    program_side = {**{k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()},
                    "shared_width": full.shared_d_ff}
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    if wrong or full.router_score != "sigmoid" or config["n_group"] != 1:
        raise RuntimeError(
            f"{config['registry_model']} is not at the file's sizes (file, program): {wrong}")
    cfg = dataclasses.replace(
        full, n_layers=config["num_hidden_layers"], vocab_size=config["vocab_size"],
        experts_held=config["n_routed_experts"],
        first_expert_held=config["deployment"]["first_expert_held"],
        mtp_loss_weight=config["mtp_loss_weight"], **overrides)

    def init(key):
        return llama.init_params(cfg, key)

    return cfg, init, llama.logical_axes(cfg)


def balanced_bias(cfg, params, make):
    """-> the selection biases, float32 [expert layers + 1, experts] (the
    MTP block's row last), under which `params` (the share `cfg`, as
    `build` gives them) route equal numbers of the pairs of `make(i)`
    (the run's batches) to every expert of a block."""
    import jax
    import numpy as np

    from ray_tpu.models import llama

    @jax.jit
    def counts(params, bias, batch):
        layers = {**params["layers"], "router_bias": bias.astype(cfg.param_dtype)}
        stats = llama.loss_and_weight_fn({**params, "layers": layers}, batch, cfg)[2]
        return stats["tokens_per_expert"]

    bias = np.zeros((cfg.n_expert_layers + cfg.mtp_layers, cfg.n_experts), np.float32)
    kept = []
    for i in range(PASSES):
        seen = np.asarray(counts(params, bias, make(FIRST_BATCH + i)), np.float64)
        step = STEP_FIRST * (STEP_LAST / STEP_FIRST) ** (i / (PASSES - 1))
        bias = bias + np.float32(step) * np.sign(seen.mean(-1, keepdims=True) - seen)
        kept.append(bias)
    return np.mean(kept[-AVERAGED:], axis=0, dtype=np.float32)
