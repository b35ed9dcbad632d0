"""Expert layer: of the expert blocks the traced steps ran, the share that ran over the bound on
the HELD rows and not over all N x top_k pair rows (%). A share cell's block branches on the
device (`jax.lax.cond`); each branch stands under a named scope of its own INSIDE `moe.dispatch` /
`moe.experts` / `moe.combine` (`moe.held`, `moe.all`), so the events of the weight-gradient
grouped-matmul kernels (three a block and step, whichever branch ran) are counted by the scope
their instruction carries in the program's record of its compiled step. None where no site was
built with the compact path (the engage counter `moe.compact`: the parent, `olmoe-train`,
`zaya1-train`), without a trace or the record, or where no such kernel ran (under a mesh)."""

import re

from chipbench import readers_step

KERNEL = "ragged-dot-tiled-wgrad"
# a scope as a whole component of a path, bare or wrapped, as readers_step matches its own
BRANCHES = {scope: re.compile(r"(?<![^/(])" + re.escape(scope) + r"(?![^/)])")
            for scope in ("moe.held", "moe.all")}


def read(run):
    if not run.get("trace"):
        return None
    from ray_tpu import obs

    counters, record = getattr(obs, "layer_counters", None), getattr(obs, "op_names", None)
    if counters is None or record is None or not counters().get("moe.compact", {}).get("count"):
        return None
    branch_of = {instruction: branch
                 for instruction, entries in (record() or {}).items()
                 if instruction.startswith(KERNEL)
                 for branch, path in BRANCHES.items() if path.search(entries[0][1])}
    ran = dict.fromkeys(BRANCHES, 0)
    lo, hi = run["win"]
    for events in run["trace"].device_ops.values():
        for name, start, seconds in events:
            branch = branch_of.get(readers_step.instruction_of(name))
            if branch is not None and start + seconds > lo and start < hi:
                ran[branch] += 1
    blocks = sum(ran.values())
    return 100.0 * ran["moe.held"] / blocks if blocks else None
