"""Lock-guard inference: which lock protects which attribute, and who
touches it without that lock.

For every class (and module) with at least one lock, the pass infers a
guard relation from the evidence the code itself provides: an attribute
that is consistently touched inside ``with self._lock:`` bodies is
treated as guarded by ``_lock``, and the remaining accesses — the ones
outside any acquisition of that lock — are exactly the TSAN-shaped bugs
PR 7/8 hit (a snapshot read racing a mutator, a reconcile writing state
the sweep thread owns).

Inference rule (tuned against this codebase; see tests/test_analysis.py):
an attribute is **guarded by L** when, excluding ``__init__``-time
construction (happens-before publication of ``self``):

 * it is WRITTEN at least once while holding L (shared *mutable* state —
   read-only config attrs set in ``__init__`` never qualify), and
 * at least ``MIN_GUARDED`` accesses hold L, and
 * at least ``GUARD_FRACTION`` of all its accesses hold L (majority
   evidence — a 50/50 attribute has no inferred discipline to enforce).

Violations are the minority accesses. Audited exceptions go in
``ALLOWLIST`` keyed by ``(file, Class.attr, function)`` with a written
invariant; stale entries fail the pass (analysis/allowlist.py).
"""

from __future__ import annotations

from ray_tpu.analysis import lockmodel
from ray_tpu.analysis.allowlist import Allowlist
from ray_tpu.analysis.walker import DEFAULT_PACKAGES, iter_files

MIN_GUARDED = 4        # accesses under L before we believe the pattern
GUARD_FRACTION = 0.75  # share of accesses that must hold L

# (file, owner.attr, function) -> justification. The function key is the
# OUTERMOST enclosing def (nested helpers inherit their parent's audit).
ALLOWLIST = Allowlist({
    ("obs/recorder.py", "SpanRecorder._layer_counts", "layer_counters"): (
        "the layer counters are read without the lock by design (the "
        "docstring says so): a scrape or LLMServer.stats() must not wait "
        "on a span that is ending; list(dict.items()) is one GIL-atomic "
        "copy and a cell's three numbers are only ever added to, so the "
        "worst a reader sees is a count whose seconds land an instant later"
    ),
    ("serve/router.py", "Router._replicas", "_refresh"): (
        "advisory staleness fast-path on the dispatch hot path: "
        "GIL-atomic reads; a stale value costs one redundant refresh RPC "
        "or 0.25s extra staleness, while locking here serializes the "
        "dispatch fan-out (burst shedding regressed measurably under it)"
    ),
    ("serve/router.py", "Router._inflight", "_pick"): (
        "power-of-two-choices is a heuristic: GIL-atomic int reads; a "
        "stale counter skews one pick toward the busier replica, never "
        "correctness — the accounting increments/decrements stay under "
        "_lock. A hot mutex on every dispatch buys nothing here"
    ),
    ("core/placement.py", "PlacementGroup._state", "__repr__"): (
        "diagnostic repr: _state is a str rebound atomically under the "
        "GIL, and a stale value in a log line is acceptable; taking "
        "_lock in __repr__ would self-deadlock any log statement issued "
        "inside a locked region"
    ),
    ("core/runtime.py", "<module>._runtime", "get_runtime"): (
        "the atexit lambda registered here runs at interpreter shutdown "
        "(single-threaded by then); taking _runtime_lock inside the "
        "atexit hook could deadlock if exit fires while another thread "
        "holds the lock"
    ),
}, label="lock-guard allowlist")


def infer_guards(model: lockmodel.FileModel,
                 ctor_funcs: set | None = None) -> dict[tuple, str]:
    """{(owner, attr): lock_ident} for every attribute whose access
    pattern clears the inference thresholds."""
    if ctor_funcs is None:
        ctor_funcs = constructor_only_funcs(model)
    by_attr: dict[tuple, list] = {}
    for acc in model.accesses:
        if (acc.owner, acc.func) in ctor_funcs:
            continue
        by_attr.setdefault((acc.owner, acc.attr), []).append(acc)
    guards: dict[tuple, str] = {}
    for key, accs in by_attr.items():
        owner = key[0]
        candidate_locks = {
            info.ident for info in model.locks.values()
            if info.owner == owner and info.kind != "semaphore"
        }
        # semaphores with count > 1 are not mutual exclusion; a
        # Condition resolves to its root before reaching `held`
        best = None
        for lock in sorted(candidate_locks):
            root = model.lock_root(*lock.split(".", 1))
            held = [a for a in accs if root in a.held]
            if not any(a.write for a in held):
                continue
            if len(held) < MIN_GUARDED:
                continue
            if len(held) / len(accs) < GUARD_FRACTION:
                continue
            if best is None or len(held) > best[1]:
                best = (root, len(held))
        if best is not None:
            guards[key] = best[0]
    return guards


CONSTRUCTORS = ("__init__", "__new__", "__post_init__")


def constructor_only_funcs(model: lockmodel.FileModel) -> set[tuple]:
    """(owner, func) pairs that only ever run during construction:
    the constructors themselves, plus private helpers whose EVERY
    self-call site is constructor-only (``_load_snapshot`` called from
    ``__init__``). Their accesses happen before ``self`` is published,
    so no lock discipline applies — and they must not count as
    unguarded evidence against an attribute either."""
    owners = set(model.class_methods)
    ctor: set[tuple] = {(o, c) for o in owners for c in CONSTRUCTORS}
    sites: dict[tuple, list] = {}
    for sc in model.self_calls:
        sites.setdefault((sc.cls, sc.callee), []).append(sc)
    for _ in range(6):
        grew = False
        for (cls, m), calls in sites.items():
            if (cls, m) in ctor:
                continue
            if not m.startswith("_") or m.startswith("__"):
                continue
            if (cls, m) in model.method_refs:
                continue
            if all((c.cls, c.func) in ctor and "." not in c.func
                   for c in calls):
                ctor.add((cls, m))
                grew = True
        if not grew:
            break
    return ctor


def check_model(model: lockmodel.FileModel,
                allowlist: Allowlist | None = None) -> list[str]:
    al = ALLOWLIST if allowlist is None else allowlist
    ctor_funcs = constructor_only_funcs(model)
    guards = infer_guards(model, ctor_funcs)
    out = []
    for acc in model.accesses:
        if (acc.owner, acc.func) in ctor_funcs:
            continue
        guard = guards.get((acc.owner, acc.attr))
        if guard is None or guard in acc.held:
            continue
        outer = acc.func.split(".", 1)[0]
        key = (model.rel, f"{acc.owner}.{acc.attr}", outer)
        if al.permits(key):
            continue
        kind = "write to" if acc.write else "read of"
        out.append(
            f"{model.rel}:{acc.line}: {kind} {acc.owner}.{acc.attr} "
            f"outside its inferred guard {guard} (in {acc.func})"
        )
    return out


def collect_violations(packages=DEFAULT_PACKAGES, root=None,
                       allowlist: Allowlist | None = None) -> list[str]:
    al = ALLOWLIST if allowlist is None else allowlist
    al.used.clear()
    out: list[str] = []
    for sf in iter_files(packages, root):
        model = lockmodel.build_file_model(sf.tree, sf.rel)
        out.extend(check_model(model, al))
    out.extend(al.problems())
    return out
