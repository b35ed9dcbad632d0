"""The causal tower of Nemotron-Labs-TwoTower-30B-A3B of the program's
registry as ONE CHIP'S SHARE of a stated deployment: depth cut (the
first `num_hidden_layers` characters of `hybrid_override_pattern`, which
stays whole in the file), `n_routed_experts` of the published experts
held (from `deployment.first_expert_held`), `vocab_size` rows of the
embedding and columns of the head held, and nothing else changed. Every
width in the configuration file must equal the registry entry's, and the
registry entry must be at the file's `published` counts, or the run
fails. The published model's SECOND tower and its diffusion objective
are not built (the configuration's `assumed`); a file that asks for
them has no key to ask with.

The weights are what `llama.init_params` gives a key, the router's
SELECTION BIASES (b of `top-k(s + b)`, which take no gradient and which
no step moves) zero among them: `params["layers"]["router_bias"]`
[expert layers, experts].

`balanced_bias` makes the table that the cell's runner puts in that
parameter's place before the first step: the bias under which every
expert of a layer sees as many of the run's own tokens as the next, the
state the balancing of arXiv:2408.15664 holds a deployment in: this chip
then holds a sixteenth of every layer's pairs. It is the rule of
model_builders/registry_glm_lite.py (a sigmoid router's), ONE fixed rule
with no option, at THIS router's constants; it is not imported from that
builder because each reads its own module's constants and its own
count of expert layers: the sign rule (b_e up by a step where expert e
saw fewer pairs than the mean, down where more), PASSES forward passes
of the program's own loss function over fresh batches of the run's
traffic, all layers at once, the step falling geometrically from
STEP_FIRST to STEP_LAST; the last AVERAGED passes' tables are averaged.
Its one program takes the weights, the table and the batch as
ARGUMENTS, so it is compiled once for all seeds."""

from __future__ import annotations

import dataclasses

# The rule's constants, fixed here and read from no file. A fresh router's
# scores are sigmoids of logits of unit variance; a token's sixth largest of
# 128 lies 1.68 deviations up (a score of 0.84) and its sixth and seventh
# 0.080 deviations = 0.0106 of a score apart (1 / (128 x the normal density
# there) x s (1 - s)). The steps are GLM's multiples of that gap, 2.5 and 0.075.
PASSES, AVERAGED = 48, 16
STEP_FIRST, STEP_LAST = 0.027, 8e-4
FIRST_BATCH = 1 << 20  # the passes' batches: far from the steps' own (0, 1, 2, ...)

# configuration-file key -> NemotronHConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "head_dim": "head_dim", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "moe_intermediate_size": "d_ff",
          "moe_shared_expert_intermediate_size": "shared_d_ff", "num_experts_per_tok": "top_k",
          "norm_topk_prob": "norm_topk_prob", "routed_scaling_factor": "routed_scaling",
          "layer_norm_epsilon": "rms_eps", "max_position_embeddings": "max_seq",
          "tie_word_embeddings": "tie_embeddings", "mamba_num_heads": "mamba_heads",
          "mamba_head_dim": "mamba_head_dim", "n_groups": "ssm_groups",
          "ssm_state_size": "ssm_state", "conv_kernel": "conv_kernel", "chunk_size": "chunk_size",
          "hybrid_override_pattern": "pattern", "time_step_min": "time_step_min",
          "time_step_max": "time_step_max", "time_step_floor": "time_step_floor"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "n_routed_experts": "n_experts",
          "vocab_size": "vocab_size"}
# what the program runs in one form only: the file must say that form
FORMS = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "use_conv_bias": True,
         "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False, "use_bias": False,
         "n_group": 1, "topk_group": 1, "n_shared_experts": 1, "sliding_window": None,
         "time_step_limit": [0, None], "rescale_prenorm_residual": True}


def build(config: dict, **overrides):
    """-> (NemotronHConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"]}
    program_side = {k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()}
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    unrun = {k: config[k] for k, form in FORMS.items() if config[k] != form}
    if (wrong or unrun or full.router_score != "sigmoid" or full.expert_act != "relu2"
            or full.published_layers != config["published"]["num_hidden_layers"]):
        raise RuntimeError(f"{config['registry_model']} is not at the file's sizes "
                           f"(file, program): {wrong}; not run: {unrun}")
    cfg = dataclasses.replace(
        full, n_layers=config["num_hidden_layers"], vocab_size=config["vocab_size"],
        experts_held=config["n_routed_experts"],
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
