"""SDAR-30B-A3B of the program's registry as ONE CHIP'S SHARE of a stated
deployment: depth cut (`num_hidden_layers`: every layer is alike),
`num_experts` of the published experts held (from
`deployment.first_expert_held`), `vocab_size` rows of the embedding and
columns of the head held, and nothing else changed. Every width in the
configuration file and the block-diffusion objective's two numbers
(`block_diffusion`: the block length, the registry entry's; the floor of
the masking probability, the objective module's one constant) must equal
the program's, and the registry entry must be at the file's `published`
counts, or the run fails. The corruption has no seed to hand over: the
step makes its key of its own count and tokens.

The weights are what `llama.init_params` gives a key, the router's
SELECTION BIASES (b of `top-k(p + b)`, which take no gradient and which
no step moves) zero among them: one table of the model,
`params["layers"]["router_bias"]` [layers, experts], in layer order.

`balanced_bias` makes the table that the cell's runner puts in that
parameter's place before the first step: the bias under which every
expert of a layer sees as many of the run's own rows as the next (both
copies' rows: the passes run the program's own loss function, which
corrupts each batch as a first step would), the state the balancing of
arXiv:2408.15664 holds a deployment in: this chip then holds an eighth of
every layer's pairs. It is the rule of
model_builders/registry_keye.py (the same router: softmax over 128, 8 a
token), ONE fixed rule with no option, at that builder's constants: the
sign rule (b_e up by a step where expert e saw fewer pairs than the mean,
down where more), PASSES forward passes over fresh batches of the run's
traffic, all layers at once, the step falling geometrically from
STEP_FIRST to STEP_LAST; the last AVERAGED passes' tables are averaged.
Its one program takes the weights, the table and the batch as ARGUMENTS,
so it is compiled once for all seeds."""

from __future__ import annotations

import dataclasses

# registry_keye.py's constants and their reason: a fresh router's 128
# probabilities have their eighth and ninth largest 1.4e-3 apart; the steps
# are 2.5 and 0.075 of that gap, and the table can travel 0.05 in 48 passes.
PASSES, AVERAGED = 48, 16
STEP_FIRST, STEP_LAST = 3.5e-3, 1e-4
FIRST_BATCH = 1 << 20  # the passes' batches: far from the steps' own (0, 1, 2, ...)

# configuration-file key -> LagunaConfig attribute: what no cut may touch
WIDTHS = {"hidden_size": "d_model", "head_dim": "head_dim", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "intermediate_size": "dense_d_ff",
          "moe_intermediate_size": "d_ff", "num_experts_per_tok": "top_k",
          "norm_topk_prob": "norm_topk_prob", "rms_norm_eps": "rms_eps",
          "rope_theta": "rope_theta", "max_position_embeddings": "max_seq",
          "tie_word_embeddings": "tie_embeddings"}
# configuration-file key -> attribute: what the share cuts, held to `published`
COUNTS = {"num_hidden_layers": "n_layers", "num_experts": "n_experts", "vocab_size": "vocab_size"}


def build(config: dict, **overrides):
    """-> (LagunaConfig of the share, init(key) -> params, logical_axes tree)."""
    from ray_tpu.models import block_diffusion, llama
    from ray_tpu.models.registry import get_model_config

    full = get_model_config(config["registry_model"])
    file_side = {**{k: config[k] for k in WIDTHS}, **config["published"],
                 "block_diffusion": config["block_diffusion"]}
    program_side = {**{k: getattr(full, a) for k, a in {**WIDTHS, **COUNTS}.items()},
                    "block_diffusion": {"block_length": full.diffusion_block,
                                        "eps": block_diffusion.EPS}}
    wrong = {k: (v, program_side[k]) for k, v in file_side.items() if v != program_side[k]}
    unrun = {k: config[k] for k in ("attention_bias", "use_sliding_window", "sliding_window",
                                    "mlp_only_layers", "rope_scaling") if config[k]}
    kinds = set(full.layer_types)
    if (wrong or unrun or full.router_score != "softmax" or full.attn_gate != "none"
            or not full.qk_head_norm or full.heads_per_layer or full.shared_d_ff
            or full.routed_scaling != 1.0 or kinds != {"full_attention"}
            or (full.rope_full.theta, full.rope_full.rope_type, full.rope_full.partial)
            != (config["rope_theta"], "default", 1.0) or full.first_dense_layers
            or config["decoder_sparse_step"] != 1 or config["hidden_act"] != "silu"):
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
    numbers of the pairs of `make(i)` (the run's batches, both copies'
    rows) to every expert of a layer."""
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
