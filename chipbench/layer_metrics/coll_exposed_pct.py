"""Train step across chips: collective time during which no other op ran on that chip,
over the traced window (%)."""

from chipbench import trace_reduce as tr


def read(run):
    if not run.get("trace"):
        return None
    sec = tr.exposed(run["trace"], run["rules"], "collective", run["win"])
    return 100.0 * sec / (run["win"][1] - run["win"][0])
