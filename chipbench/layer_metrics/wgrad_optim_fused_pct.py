"""Train step: share of the traced steps' device time in fusions that hold a matmul under a model
scope AND instructions of the optimizer's update (%): booked to the model scope in the families,
counted here a second time. None without a trace or the program's record."""

from chipbench import readers_step


def read(run):
    return readers_step.fused_with_optim_pct(run)
