"""Train step: train_tok_s x ACTIVE operations a token requires (costs_moe: a token's own
num_experts_per_tok experts, the router, attention, the head; recompute not counted) over
chips x peak FLOP/s (%)."""

from chipbench import costs_moe


def read(run):
    rate = run["values"]["train_tok_s"]
    if not rate or "num_experts" not in run["shape"]:
        return None
    per_token = costs_moe.train_flops_per_token(run["shape"], run["traffic"]["seq_len"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
