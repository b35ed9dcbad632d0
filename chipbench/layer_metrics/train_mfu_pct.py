"""Train step: train_tok_s x operations a token requires (the benchmark's own function,
recompute not counted) over chips x peak FLOP/s (%)."""

from chipbench import costs


def read(run):
    rate = run["values"]["train_tok_s"]
    if not rate:
        return None
    per_token = costs.train_flops_per_token(run["shape"], run["traffic"]["seq_len"])
    return 100.0 * rate * per_token / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
