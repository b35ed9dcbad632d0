"""ZAYA1 of the program's registry as ONE CHIP'S SHARE of a stated
deployment: depth cut, `num_experts` of the published experts held
(from `deployment.first_expert_held`), `vocab_size` rows of the tied
table held, and nothing else changed. Every width in the configuration
file must equal the registry entry's, and the registry entry must be at
the file's `published` counts, or the run fails.

The weights are what `llama.init_params` gives a key, the router's
SELECTION BIAS (b of `argmax(p + b)`, which takes no gradient and which
no step moves: the report's update rule for it is not implemented in the
program) zero among them; `build` takes the configuration and hands out
an init that takes a key, and nothing of the traffic reaches it.

`balanced_bias` makes the table [layers, experts] that the cell's runner
puts in that parameter's place before the first step: the bias under
which a layer's experts see equal numbers of the run's own tokens, which
is the state ZAYA1's balancing holds a deployment in (each chip of the
pair then holds half of every layer's pairs). With b = 0 the experts of this chip's share see
41 to 58% of the pairs by the seed (a Zipf(1.1) unigram puts 9% of the
tokens on one id, and a fresh router sends an id's tokens to one expert)
and the cell's rate follows the share by 1.4% for every ten points
(PERF.md section 6, PR 32). It is ONE fixed rule with no option: the
sign rule of arXiv:2408.15664 (b_e up by a step where expert e saw fewer
tokens than the mean, down where more), PASSES forward passes of the
program's own loss function over fresh batches of the run's traffic,
all layers at once, the step falling geometrically from STEP_FIRST to
STEP_LAST; the last AVERAGED passes' tables are averaged, which takes
out the rule's own oscillation. Its one program takes the weights, the
table and the batch as ARGUMENTS, so it is compiled once for all seeds
(as a constant of the init program the table would compile that program
anew for every seed: 34 s on the chip)."""

from __future__ import annotations

import dataclasses

# The rule's constants, fixed here and read from no file. A fresh
# router's probabilities lie within a few hundredths of 1/16: a step of
# 0.01 crosses them in a few passes (0.03 only swings the experts past
# each other), the last step moves a few tokens in a thousand. Tried at
# the published widths on the CPU (PERF.md section 6, PR 32): the share
# held goes from 56.8% to 50.3%, every layer within 2.3 points.
PASSES, AVERAGED = 48, 16
STEP_FIRST, STEP_LAST = 0.01, 0.0003
FIRST_BATCH = 1 << 20  # the passes' batches: far from the steps' own (0, 1, 2, ...)

# configuration-file key -> ZayaConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "moe_intermediate_size": "d_ff",
          "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
          "head_dim": "head_dim", "router_hidden_size": "router_hidden",
          "num_experts_per_tok": "top_k", "partial_rotary_factor": "rotary_fraction",
          "rms_norm_eps": "rms_eps", "max_position_embeddings": "max_seq",
          "tie_word_embeddings": "tie_embeddings"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "num_experts": "n_experts",
          "vocab_size": "vocab_size"}


def build(config: dict, **overrides):
    """-> (ZayaConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"],
                 "cca_time": (config["cca_time0"], config["cca_time1"]),
                 "rope_theta": config["rope_parameters"]["hybrid"]["rope_theta"]}
    program_side = {**{k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()},
                    "cca_time": tuple(full.conv_kernels), "rope_theta": full.rope_theta}
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    if wrong or full.router_kind != "mlp":
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
    """-> the selection bias, float32 [layers, experts], under which
    `params` (the share `cfg`, as `build` gives them) route equal
    numbers of the tokens of `make(i)` (the run's batches) to every
    expert of a layer."""
    import jax
    import numpy as np

    from ray_tpu.models import llama

    @jax.jit
    def counts(params, bias, batch):
        layers = {**params["layers"], "router_bias": bias.astype(cfg.param_dtype)}
        stats = llama.loss_and_weight_fn({**params, "layers": layers}, batch, cfg)[2]
        return stats["tokens_per_expert"]

    bias = np.zeros((cfg.n_layers, cfg.n_experts), np.float32)
    kept = []
    for i in range(PASSES):
        seen = np.asarray(counts(params, bias, make(FIRST_BATCH + i)), np.float64)
        step = STEP_FIRST * (STEP_LAST / STEP_FIRST) ** (i / (PASSES - 1))
        bias = bias + np.float32(step) * np.sign(seen.mean(-1, keepdims=True) - seen)
        kept.append(bias)
    return np.mean(kept[-AVERAGED:], axis=0, dtype=np.float32)
