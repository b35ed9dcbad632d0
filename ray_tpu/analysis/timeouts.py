"""Static blocking-call timeout lint (the original ``check_timeouts``,
now on the shared analysis framework).

The control plane's availability story (heartbeat death verdicts, lease
retries, chaos-driven failover) only works if no thread can block
FOREVER on a peer that silently died: every blocking socket/RPC receive
in ``ray_tpu/cluster/``, ``ray_tpu/native/``, ``ray_tpu/collective/``
and ``ray_tpu/dag/`` must carry an explicit timeout. Fails on:

 * ``settimeout(None)`` — an explicit opt-in to unbounded blocking;
 * bare receive-family calls (``recv`` / ``recv_into`` / ``recvfrom`` /
   ``recv_bytes`` / ``readexactly`` / ``accept``) with no ``timeout``
   argument in a scope that never set a bounded socket timeout;
 * zero-argument ``.wait()`` / ``.get()`` / ``.result()`` — unbounded
   thread parks (Event/Condition/queue/Future);
 * ``wait_for``/``kv_wait`` without their timeout operand.

Audited exceptions live in ``ALLOWLIST`` (analysis/allowlist.py: every
entry carries a justification, stale entries are violations).

CLI shim: ``python scripts/check_timeouts.py`` (exit 1 on problems).
"""

from __future__ import annotations

import ast
import os

from ray_tpu.analysis.allowlist import Allowlist
from ray_tpu.analysis.walker import FuncStackVisitor, call_name, has_kwarg, repo_root

RECV_CALLS = {
    "recv", "recv_into", "recvfrom", "recv_bytes", "readexactly", "accept",
}
PARK_CALLS = {"wait", "get", "result"}
# park-calls whose timeout is a REQUIRED trailing positional (or kwarg):
# Condition.wait_for(pred[, timeout]) and the GCS kv_wait(key, ns,
# timeout) — the collective plane's rendezvous primitives. Calling them
# without the timeout operand is an unbounded park.
BOUNDED_PARK_MIN_ARGS = {"wait_for": 2, "kv_wait": 3}

# (path suffix, enclosing function name, call attr) -> reason
ALLOWLIST = Allowlist({
    ("cluster/rpc.py", "connect", "settimeout"): (
        "clears create_connection's lingering timeout: timeout-mode "
        "sendall can abandon a frame mid-write (bytes sent indeterminate) "
        "and corrupt the stream; sends must block, the read loop bounds "
        "itself with select() polls"
    ),
    ("cluster/rpc.py", "_on_conn", "readexactly"): (
        "asyncio server-side connection reader: a stalled client parks one "
        "coroutine (not a thread); connection close/cancellation unblocks it"
    ),
    ("cluster/gcs_service.py", "main", "wait"): (
        "daemon main(): intentional forever-park of the entry thread; "
        "SIGINT/SIGTERM are the designed wakeups"
    ),
    ("cluster/node_daemon.py", "main", "wait"): (
        "daemon main(): intentional forever-park; SIGTERM triggers the "
        "graceful-drain handler"
    ),
    ("cluster/worker_main.py", "main", "wait"): (
        "worker main(): intentional forever-park; the daemon kills the "
        "process when its lease ends"
    ),
    ("ops/ssd.py", "_bwd_kernel", "wait"): (
        "a Pallas DMA descriptor's wait, traced into the kernel (a "
        "semaphore wait on the device for a copy the same step started): "
        "no host thread parks on it"
    ),
})

SCAN_DIRS = (
    "ray_tpu/cluster", "ray_tpu/native", "ray_tpu/collective",
    # r13: the compiled-DAG channel plane — exec loops ride the same
    # peer-may-die substrate as the collectives, so its reads/parks must
    # be bounded too (ChannelTimeoutError instead of a hung loop)
    "ray_tpu/dag",
    # r15: the fabric transfer plane — endpoint receives must poll
    # bounded (a transfer plane never parks a consumer loop forever)
    "ray_tpu/fabric",
    # r17: the tiered prefix cache — object-store gets and index RPCs
    # sit on the prefill admission path, so every park must be bounded
    "ray_tpu/llm/kvtier",
    # r18: the cross-engine fetch plane + prefetch/spill workers — a
    # dead fetch source or a stalled endpoint must fail typed within
    # its bound, and the worker loops must park in bounded slices
    "ray_tpu/llm/kvfetch",
    # r19: the RL post-training planes — a starved trajectory queue or
    # a wedged publish must park in bounded slices (the learner gang's
    # fault detector must never be the thing that notices)
    "ray_tpu/rl/post_train",
    # r20: the autoscale controller — signal fetches and actuator calls
    # cross the RPC plane, so every wait must carry its bound
    "ray_tpu/autoscale",
    # r21: the fleet plane — request submission crosses replica runner
    # queues and the canary ladder polls SLO grades; both must park in
    # bounded slices
    "ray_tpu/fleet",
    # r24: the kernel tier (pure jax/pallas — no parks today, but ops
    # code grows host callbacks and test harnesses; scanning from day
    # one keeps the floor in place) and the mixed-batch planner, which
    # sits directly on the engine's step path
    "ray_tpu/ops",
    "ray_tpu/llm/mixed.py",
)


class _Linter(FuncStackVisitor):
    def __init__(self, rel_path: str):
        super().__init__()
        self.rel = rel_path
        # scopes where a bounded settimeout() was seen (function names)
        self.bounded_scopes: set[str] = set()
        self.violations: list[str] = []
        self.used_allowlist: set[tuple] = set()

    def _allowed(self, call_name_: str) -> bool:
        for fn in self.func_stack or ["<module>"]:
            key = (self.rel, fn, call_name_)
            if ALLOWLIST.permits(key):
                self.used_allowlist.add(key)
                return True
        return False

    # -- the rules ------------------------------------------------------------

    def visit_Call(self, node: ast.Call):
        name = call_name(node)
        if name == "settimeout":
            args = node.args
            if args and isinstance(args[0], ast.Constant) and args[0].value is None:
                if not self._allowed("settimeout"):
                    self.violations.append(
                        f"{self.rel}:{node.lineno}: settimeout(None) — "
                        "unbounded socket block; set a poll timeout and "
                        "re-check a stop flag"
                    )
            elif args:
                for fn in self.func_stack:
                    self.bounded_scopes.add(fn)
        elif name == "select" and len(node.args) >= 4:
            # select.select(r, w, x, timeout): a readability poll with a
            # timeout bounds the recv that follows it in this scope
            for fn in self.func_stack:
                self.bounded_scopes.add(fn)
        elif name in RECV_CALLS and isinstance(node.func, ast.Attribute):
            covered = any(fn in self.bounded_scopes for fn in self.func_stack)
            if not covered and not has_kwarg(node, "timeout"):
                if not self._allowed(name):
                    self.violations.append(
                        f"{self.rel}:{node.lineno}: blocking {name}() with no "
                        "timeout in scope (no bounded settimeout on this "
                        "path, no timeout= argument)"
                    )
        elif (
            name in PARK_CALLS
            and isinstance(node.func, ast.Attribute)
            and not node.args
            and not node.keywords
        ):
            if not self._allowed(name):
                self.violations.append(
                    f"{self.rel}:{node.lineno}: zero-argument .{name}() — "
                    "unbounded park; pass a timeout and loop on a stop flag"
                )
        elif (
            name in BOUNDED_PARK_MIN_ARGS
            and isinstance(node.func, ast.Attribute)
            and len(node.args) < BOUNDED_PARK_MIN_ARGS[name]
            and not has_kwarg(node, "timeout")
        ):
            if not self._allowed(name):
                self.violations.append(
                    f"{self.rel}:{node.lineno}: .{name}() without its "
                    "timeout operand — unbounded park on a peer that may "
                    "never arrive"
                )
        self.generic_visit(node)


def lint_source(src: str, rel_path: str,
                used_allowlist: "set | None" = None) -> list[str]:
    """Lint one file's source; returns violation strings. Consumed
    ALLOWLIST keys are added to ``used_allowlist`` when given."""
    tree = ast.parse(src)
    # two passes: settimeout()/select() may appear after a nested
    # function's definition but cover calls made at runtime — collect
    # bounded scopes first, then judge
    first = _Linter(rel_path)
    first.visit(tree)
    second = _Linter(rel_path)
    second.bounded_scopes = first.bounded_scopes
    second.visit(tree)
    if used_allowlist is not None:
        used_allowlist.update(second.used_allowlist)
    return second.violations


def collect_violations(repo_root_: str | None = None) -> list[str]:
    root = repo_root_ or repo_root()
    out: list[str] = []
    ALLOWLIST.used.clear()
    used: set = set()
    for scan in SCAN_DIRS:
        base = os.path.join(root, scan)
        if os.path.isfile(base):
            # single-file entries (e.g. ray_tpu/llm/mixed.py) — os.walk
            # on a file path yields nothing and would silently scan zero
            # lines
            paths = [base]
        else:
            paths = [
                os.path.join(dirpath, f)
                for dirpath, _dirs, files in os.walk(base)
                for f in sorted(files)
                if f.endswith(".py")
            ]
        for path in paths:
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            rel = rel.removeprefix("ray_tpu/")
            with open(path, encoding="utf-8") as fh:
                out.extend(lint_source(fh.read(), rel, used))
    # the shared allowlist self-audit: unjustified entries + stale
    # entries (an audited exception that no longer matches any code is a
    # lie waiting to mask the next unbounded call under the same key)
    ALLOWLIST.used.update(used)
    out.extend(ALLOWLIST.problems())
    return out


def main() -> int:
    problems = collect_violations()
    if problems:
        print(f"check_timeouts: {len(problems)} problem(s)")
        for p in problems:
            print(f"  {p}")
        return 1
    print("check_timeouts: ok")
    return 0
