"""Train step: device time booked to the block-diffusion objective's own scopes, `diff.corrupt`
(the levels, the mask draws, the second copy, the positions) + `diff.loss` (the weights m_i / p_b,
the head's matmuls and the weighted cross-entropy over the L noised rows, forward and backward,
and the step's counts), % of the traced window's busy time (the family `diff` of
chipbench/step_scopes/sdar.json). `diff.loss` stands INSIDE `head`, after the final norm, and an
operation is booked to its innermost scope: `head_share_pct` of this cell is the final norm alone."""

from chipbench import readers_sdar


def read(run):
    return readers_sdar.diff_share_pct(run)
