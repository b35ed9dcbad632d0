"""Trainer: what the window's slow steps (`step_stalls.train`) cost `train_tok_s` in this
run: the sum over them of (period - the window's median period), over the window's seconds,
in %. 0.0 where no step was slow; None without a timeline."""

from chipbench import readers_timeline


def read(run):
    return readers_timeline.stall_loss_pct(run)
