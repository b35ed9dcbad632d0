"""A sparse-expert model of the program's registry with depth cut and
nothing else changed: what registry_llama checks (it builds the model),
and the expert layer's sizes on top, each equal to the registry
entry's or the run fails."""

from __future__ import annotations

from chipbench import manifest as mf

# configuration-file key -> MoEConfig attribute
EXPERT_WIDTHS = {"num_experts": "n_experts", "num_experts_per_tok": "top_k",
                 "norm_topk_prob": "norm_topk_prob",
                 "router_aux_loss_coef": "router_aux_coeff",
                 "router_z_loss_coef": "router_z_coeff"}


def build(config: dict, **overrides):
    """-> (MoEConfig, init(key) -> params, logical_axes tree)."""
    dense = mf.load_plugin(mf.ROOT, "model_builders", "registry_llama")
    cfg, init, axes = dense.build(config, **overrides)
    wrong = {k: (config[k], getattr(cfg, a, None)) for k, a in EXPERT_WIDTHS.items()
             if config[k] != getattr(cfg, a, None)}
    if wrong or not cfg.qk_norm:
        raise RuntimeError(
            f"{config['registry_model']} is not at the file's sizes (file, program): {wrong}"
            f"{'' if cfg.qk_norm else '; it has no q/k norm'}")
    return cfg, init, axes
