"""Model programs: share of the traced steps' device time in operations booked to the `head`
and `mtp.head` scopes: the final norm, the head's three matmuls, the loss (%). A weight gradient
fused with AdamW's update counts here, by the matmul it holds (chipbench/readers_step.py has the
rule; `wgrad_optim_fused_pct` says how much of it). None without a trace or the program's record."""

from chipbench import readers_step


def read(run):
    return readers_step.family_pct(run, "head")
