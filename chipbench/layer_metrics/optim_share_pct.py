"""Train step: share of the traced steps' device time in operations booked to the `optim` scope
ALONE: the update where no matmul of the model is fused with it, the global norm (%). The update
that rides on a weight gradient is `wgrad_optim_fused_pct`. None without a trace or the record."""

from chipbench import readers_step


def read(run):
    return readers_step.family_pct(run, "optim")
