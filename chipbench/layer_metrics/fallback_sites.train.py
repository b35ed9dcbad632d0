"""Kernels: call sites of the step that took the plain path where an overlapped `tp` ring or a
Pallas grouped matmul stands (the program's engage counters `tp_overlap.plain` +
`grouped_matmul.ragged_dot`, counted while tracing, in the harness's process). 0 where every site
engaged; None where no site of either kind was counted on either path, or nothing trained."""

from chipbench import readers_step


def read(run):
    return readers_step.fallback_sites(run)
