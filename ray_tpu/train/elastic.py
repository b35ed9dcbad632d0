"""ray_tpu.train.elastic — fault-tolerant gang training.

The serving path survives everything ``ray_tpu.chaos`` injects (r09);
this module closes the same loop for the trainer. A
``TrainerSupervisor`` drives a data-parallel gang whose in-loop
allreduce rides ``ray_tpu.collective`` — the plane the r12 chaos kinds
(``KILL_RANK``, ``STALL_COLLECTIVE``, ``DROP_COLLECTIVE``,
``PARTIAL_PARTITION``) break — and recovers from every one of them:

 1. **detect**: every collective op is bounded (collective/errors.py),
    so a dead/stalled/partitioned rank surfaces as a typed
    ``CollectiveError`` (or the victim's ``RankKilled``) within the
    step timeout instead of hanging the pod;
 2. **abort**: ``abort_collective_group`` wakes every survivor blocked
    in the broken round immediately — nobody burns the full timeout
    waiting on a rank already known dead;
 3. **re-form**: the gang re-joins the SAME group name at gang epoch
    ``gen + 1`` — with a replacement rank (same world size) when
    allowed, else shrunk toward ``min_world_size``. The generation
    guard makes zombies harmless: a stale rank's collective ops raise
    ``StaleGenerationError`` and its late deposits land under old-gen
    keys nobody reads — it can never inject gradients into the new
    gang;
 4. **restore**: state comes back from the last complete checkpoint
    (``train/checkpoint.py`` — crash-atomic, partial dirs pruned);
 5. **resume**: batches derive ONLY from ``(seed, step, world_size,
    rank)`` via a counter-based seed stream, so resuming at the same
    world size is loss-identical to the uninterrupted run (gated by
    ``benchmarks/train_chaos_bench.py`` → ``TRAIN_chaos_r12.json``).

Observability: recoveries run under a ``train.recovery`` obs span and
move the ``ray_tpu_train_gang_epoch`` gauge /
``ray_tpu_train_recoveries_total`` + ``ray_tpu_train_ranks_lost_total``
counters (telemetry-aggregated, so ``ray_tpu status`` shows trainer
health next to the pool SLOs).

"Podracer architectures for scalable RL" (PAPERS.md) assumes exactly
this: decoupled pools that survive pool churn; "Exploring the limits of
Concurrency in ML Training on Google TPUs" motivates keeping the
recovery cost bounded (detect within the step timeout, restore only
what the checkpoint cadence lost).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import numpy as np

from ray_tpu.chaos.harness import RankKilled
from ray_tpu.collective import (
    CollectiveAbortedError,
    CollectiveError,
    CollectivePartitionError,
    CollectiveTimeoutError,
    StaleGenerationError,
    abort_collective_group,
    declare_collective_group,
    destroy_collective_group,
)
from ray_tpu.cluster.client import ActorDiedError as ClusterActorDiedError
from ray_tpu.cluster.client import ClusterTaskError
from ray_tpu.core import api
from ray_tpu.core.errors import (
    ActorDiedError,
    ActorUnavailableError,
    TaskError,
    WorkerCrashedError,
)
from ray_tpu.train.checkpoint import (
    Checkpoint,
    CheckpointManager,
    latest_complete,
)
from ray_tpu.utils.logging import get_logger

logger = get_logger("ray_tpu.train.elastic")


# -- observability ------------------------------------------------------------


def register_metrics() -> dict:
    """Trainer-health metrics (scripts/check_metrics.py hook). All three
    are telemetry-aggregated: the gang epoch rolls up as MAX (the
    fleet's current generation), the counters as SUM.

    Constructed per call, not cached (the obs/slo.py convention):
    same-name re-registration shares storage in util/metrics, and
    re-constructing means a test's ``clear_registry()`` can never strand
    a stale cached instance writing to storage the exporter no longer
    renders. These fire once per recovery, not per step."""
    from ray_tpu.obs.telemetry import AGG_MAX, cluster_counter, cluster_gauge

    _METRICS: dict = {}
    _METRICS["gang_epoch"] = cluster_gauge(
        "ray_tpu_train_gang_epoch",
        description="elastic trainer: current gang epoch (generation) — "
        "bumps on every recovery re-form; zombie ranks of older epochs "
        "are refused by the collective generation guard",
        agg=AGG_MAX,
    )
    _METRICS["recoveries"] = cluster_counter(
        "ray_tpu_train_recoveries_total",
        description="elastic trainer: completed gang recoveries "
        "(abort -> re-form -> checkpoint restore -> resume)",
    )
    _METRICS["ranks_lost"] = cluster_counter(
        "ray_tpu_train_ranks_lost_total",
        description="elastic trainer: ranks lost to kill/stall/partition "
        "across all recoveries",
    )
    _METRICS["blackouts"] = cluster_counter(
        "ray_tpu_train_blackouts_total",
        description="elastic trainer: control-plane blackouts ridden out "
        "(GCS dark -> wait -> resume; no ranks blamed, no recovery "
        "budget burned)",
    )
    return _METRICS


# -- deterministic seed stream ------------------------------------------------


def rng_for(seed: int, step: int, rank: int = 0) -> np.random.Generator:
    """The trainer's seed stream: a counter-based generator keyed ONLY by
    ``(seed, step, rank)`` — no global RNG state to checkpoint, so a
    resume replays the exact batch sequence of the uninterrupted run."""
    return np.random.default_rng(
        np.random.SeedSequence(entropy=int(seed), spawn_key=(int(step), int(rank)))
    )


# -- gradient packing ---------------------------------------------------------


def _pack(loss: float, grads: Any) -> tuple[np.ndarray, Any, list]:
    """[loss, flat grads] as one float64 vector — one allreduce per step,
    and rank-ordered float64 summation so the reduced result is bitwise
    deterministic (the loss-identity contract depends on it)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    arrs = [np.asarray(leaf) for leaf in leaves]
    flat = [np.ravel(a).astype(np.float64) for a in arrs]
    vec = np.concatenate([np.asarray([loss], np.float64)] + flat) if flat else (
        np.asarray([loss], np.float64)
    )
    shapes = [(a.shape, a.dtype) for a in arrs]
    return vec, treedef, shapes


def _unpack(vec: np.ndarray, treedef, shapes) -> tuple[float, Any]:
    import jax

    loss = float(vec[0])
    leaves = []
    off = 1
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(vec[off:off + n].reshape(shape).astype(dtype))
        off += n
    return loss, jax.tree_util.tree_unflatten(treedef, leaves)


# -- the gang member ----------------------------------------------------------


@api.remote
class _ElasticRank:
    """One gang member. Holds the replicated state; each step computes
    local gradients on its deterministic shard, allreduces
    ``[loss, grads]``, applies the mean — so every rank ends every step
    with identical state and rank 0's copy is THE checkpoint."""

    def __init__(self, grad_fn, apply_fn, batch_fn, seed: int,
                 group_name: str, step_timeout_s: float, backend: str):
        self._grad_fn = grad_fn
        self._apply_fn = apply_fn
        self._batch_fn = batch_fn
        self._seed = int(seed)
        self._group = group_name
        self._timeout = float(step_timeout_s)
        self._backend = backend
        self._state: Any = None
        self._rank = -1
        self._world = 0
        self._gen = -1

    def join(self, world_size: int, rank: int, gen: int) -> bool:
        """(Re-)join the gang at a gang epoch: recovery re-forms the SAME
        group name at gen + 1, superseding (and waking) the old one."""
        from ray_tpu.collective import init_collective_group

        init_collective_group(
            world_size, rank, backend=self._backend,
            group_name=self._group, gen=gen,
        )
        self._rank, self._world, self._gen = rank, world_size, gen
        return True

    def set_state(self, state: Any) -> bool:
        self._state = state
        return True

    def get_state(self) -> Any:
        return self._state

    def run_steps(self, start_step: int, n_steps: int) -> list:
        """Run ``n_steps`` data-parallel steps; returns per-step mean
        losses. Any gang fault surfaces as a typed error within the
        step timeout — never a hang."""
        from ray_tpu.collective import allreduce

        losses = []
        for step in range(start_step, start_step + n_steps):
            batch = self._batch_fn(self._seed, step, self._world, self._rank)
            loss, grads = self._grad_fn(self._state, batch)
            vec, treedef, shapes = _pack(float(loss), grads)
            total = allreduce(
                vec, group_name=self._group, rank=self._rank,
                timeout=self._timeout,
            )
            mean_loss, mean_grads = _unpack(
                np.asarray(total, np.float64) / self._world, treedef, shapes
            )
            self._state = self._apply_fn(self._state, mean_grads)
            losses.append(mean_loss)
        return losses


# -- supervisor ---------------------------------------------------------------


@dataclasses.dataclass
class ElasticConfig:
    """Knobs of the recovery loop."""

    world_size: int = 2
    min_world_size: int = 1
    group_name: str = "elastic"
    backend: str = "host"          # "host" (thread gang) | "cluster"
    seed: int = 0
    step_timeout_s: float = 15.0   # bound on every collective op
    steps_per_round: int = 1       # steps dispatched per supervision round
    checkpoint_every: int = 10     # steps between checkpoints
    num_to_keep: Optional[int] = 3
    max_recoveries: int = 8
    allow_replacement: bool = True  # spawn a fresh rank vs shrink
    sharded_checkpoints: bool = True  # orbax path vs pickle
    # control-plane blackout contract (r13): when the probe says the GCS
    # itself is dark, a failed round is NOBODY's fault — the supervisor
    # parks (bounded) until the plane answers again, re-forms the SAME
    # gang at gen+1, restores, and resumes. No rank is killed, nothing
    # lands in `recoveries`, and max_recoveries is untouched: a blackout
    # may only cost scheduling freshness, never gang health.
    control_plane_probe: Optional[Callable[[], bool]] = None
    # optional restart detector: sampled before each round and again at
    # fault time — a CHANGED value means the control plane restarted
    # during the round (the typed errors often only surface once the
    # plane answers again, when a probe would already say "fine"), which
    # is a blackout even if the plane is back up by classification time
    control_plane_epoch: Optional[Callable[[], Any]] = None
    blackout_wait_s: float = 60.0   # bound on waiting for the GCS to return
    blackout_poll_s: float = 0.25   # probe cadence while waiting
    max_blackouts: int = 8          # flap bound; beyond it, normal recovery

    def __post_init__(self):
        if not 1 <= self.min_world_size <= self.world_size:
            raise ValueError(
                f"need 1 <= min_world_size <= world_size, got "
                f"{self.min_world_size}/{self.world_size}"
            )
        if self.checkpoint_every < 1 or self.steps_per_round < 1:
            raise ValueError("checkpoint_every/steps_per_round must be >= 1")


@dataclasses.dataclass
class Recovery:
    """Post-mortem record of one recovery."""

    step: int              # first step of the aborted round
    resumed_from: int      # step the checkpoint restored to
    gen: int               # gang epoch AFTER the re-form
    world_size: int        # world size AFTER the re-form
    ranks_lost: int
    cause: str             # rank_killed | stall | partition | rank_died
    detect_s: float        # fault -> all survivors unblocked
    recover_s: float       # fault -> training resumed


@dataclasses.dataclass
class ElasticResult:
    state: Any
    losses: list           # per-step mean loss, full run
    recoveries: list       # [Recovery]
    completed: bool
    final_gen: int
    final_world_size: int
    checkpoint: Optional[Checkpoint] = None
    error: Optional[BaseException] = None
    # control-plane blackouts ridden out (Recovery records with
    # cause="control_plane_blackout", ranks_lost=0) — deliberately NOT
    # in `recoveries`: a dark GCS is never attributed to the gang
    blackouts: list = dataclasses.field(default_factory=list)


def _classify(err: BaseException) -> Optional[str]:
    """Fault classes for a failed rank ref. Returns None for errors that
    mean 'collateral of someone else's fault' (aborted round, stale
    generation, a survivor's own expired wait) — those ranks SURVIVED."""
    # both actor runtimes wrap task-side exceptions with the original in
    # .cause (in-process TaskError, cluster ClusterTaskError) — unwrap
    # the whole chain and classify the raiser's exception, else every
    # cluster-backend fault misreads as rank death (innocent teardown)
    seen: set[int] = set()
    while id(err) not in seen:
        seen.add(id(err))
        cause = getattr(err, "cause", None)
        if isinstance(err, (TaskError, ClusterTaskError)) and isinstance(
            cause, BaseException
        ):
            err = cause
        else:
            break
    if isinstance(err, RankKilled):
        return "rank_killed"
    if isinstance(err, CollectivePartitionError):
        return "partition"
    if isinstance(err, (ActorDiedError, ActorUnavailableError,
                        WorkerCrashedError, ClusterActorDiedError)):
        return "rank_died"
    if isinstance(err, (CollectiveAbortedError, StaleGenerationError)):
        return None
    if isinstance(err, CollectiveTimeoutError):
        # a rank whose own wait expired is a SURVIVOR of a peer's fault
        # (the faulty rank raises kill/partition in its own frame)
        return None
    if isinstance(err, CollectiveError):
        return "collective_error"
    return "rank_died"  # unknown actor-side failure: treat as lost


class TrainerSupervisor:
    """Detect -> abort -> re-form -> restore -> resume, until
    ``total_steps`` complete or the recovery budget is spent.

    ``grad_fn(state, batch) -> (loss, grads)``,
    ``apply_fn(state, mean_grads) -> state``,
    ``batch_fn(seed, step, world_size, rank) -> batch`` (must be pure in
    its arguments — that purity IS the deterministic-resume contract),
    ``init_fn(seed) -> state``.
    """

    def __init__(
        self,
        *,
        init_fn: Callable[[int], Any],
        grad_fn: Callable[[Any, Any], tuple],
        apply_fn: Callable[[Any, Any], Any],
        batch_fn: Callable[[int, int, int, int], Any],
        total_steps: int,
        checkpoint_root: str,
        config: Optional[ElasticConfig] = None,
        on_round: Optional[Callable[[int, Callable[[], Any]], None]] = None,
    ):
        self._init_fn = init_fn
        self._grad_fn = grad_fn
        self._apply_fn = apply_fn
        self._batch_fn = batch_fn
        # post-round hook ``on_round(step, state_fn)``: called after
        # every SUCCESSFUL round with the step just completed and a
        # zero-or-one-fetch state thunk (the checkpoint fetch is reused
        # when the round also checkpointed). This is how a consumer
        # wires the gang's post-step state into an external plane —
        # e.g. ``WeightPublisher.publish`` for RL post-training
        # (rl/post_train) — without coupling the supervisor to it. Hook
        # exceptions are logged and swallowed: a broken downstream
        # plane must never fault a healthy gang.
        self._on_round = on_round
        self._total_steps = int(total_steps)
        self._cfg = config or ElasticConfig()
        self._root = checkpoint_root
        self._manager = CheckpointManager(
            checkpoint_root, num_to_keep=self._cfg.num_to_keep
        )
        self._metrics = register_metrics()
        self._workers: list = []
        self._gen = 0
        self._world = self._cfg.world_size
        self._last_faults: dict[int, BaseException] = {}
        self.recoveries: list[Recovery] = []
        self.blackouts: list[Recovery] = []

    # -- gang lifecycle -------------------------------------------------------

    def _spawn_gang(self, world: int, gen: int, state: Any,
                    survivors: Optional[list] = None) -> None:
        """(Re-)form the gang: reuse healthy survivors, spawn the rest,
        everyone joins at ``gen`` and loads ``state``."""
        cfg = self._cfg
        # ranks join from their own processes, so the supervisor must
        # DECLARE the gang or its abort_collective_group/
        # destroy_collective_group calls no-op for a cluster backend
        # (no local group object, GCS abort marker never published,
        # leaked gen key poisons the next run of this group name)
        declare_collective_group(world, cfg.backend, cfg.group_name)
        pool = list(survivors or [])
        while len(pool) < world:
            pool.append(_ElasticRank.remote(
                self._grad_fn, self._apply_fn, self._batch_fn, cfg.seed,
                cfg.group_name, cfg.step_timeout_s, cfg.backend,
            ))
        self._workers = pool[:world]
        api.get(
            [w.join.remote(world, rank, gen)
             for rank, w in enumerate(self._workers)],
            timeout=60,
        )
        api.get([w.set_state.remote(state) for w in self._workers], timeout=60)
        self._gen = gen
        self._world = world
        self._metrics["gang_epoch"].set(float(gen))

    def _teardown(self) -> None:
        for w in self._workers:
            try:
                api.kill(w)
            except Exception:  # noqa: BLE001
                pass
        self._workers = []

    def _fetch_state(self) -> Any:
        """Every rank ends every step with identical state, so ANY
        healthy rank's copy is THE checkpoint — a rank that died after
        the round completed must not crash the fetch (its death is
        detected and recovered at the next dispatch)."""
        last: Optional[BaseException] = None
        for w in self._workers:
            try:
                return api.get(w.get_state.remote(), timeout=60)
            except BaseException as e:  # noqa: BLE001
                last = e
        raise last if last is not None else RuntimeError("gang is empty")

    # -- checkpointing --------------------------------------------------------

    def _save(self, state: Any, step: int) -> None:
        ckpt = Checkpoint.from_state(
            {"state": state, "step": np.asarray(step, np.int64)},
            self._manager.new_checkpoint_dir(),
            sharded=self._cfg.sharded_checkpoints,
        )
        self._manager.register(ckpt, {"step": step})

    def _restore(self) -> tuple[Any, int]:
        """State + step to resume from: the latest complete checkpoint
        (pinned against num_to_keep eviction while loading), else
        a fresh init at step 0."""
        ckpt = self._manager.latest() or latest_complete(self._root)
        if ckpt is None:
            return self._init_fn(self._cfg.seed), 0
        with self._manager.restoring(ckpt):
            doc = ckpt.load_state()
        return doc["state"], int(np.asarray(doc["step"]))

    # -- control-plane blackout -----------------------------------------------

    def _control_plane_ok(self) -> bool:
        """True when the GCS answers (or no probe is configured — then
        blackout handling is off and every fault takes the normal path)."""
        probe = self._cfg.control_plane_probe
        if probe is None:
            return True
        try:
            return bool(probe())
        except Exception:  # noqa: BLE001 — a probe failure IS "dark"
            return False

    def _await_control_plane(self) -> bool:
        """Park until the probe answers again (bounded). True = the plane
        returned within blackout_wait_s."""
        deadline = time.monotonic() + self._cfg.blackout_wait_s
        while time.monotonic() < deadline:
            if self._control_plane_ok():
                return True
            time.sleep(self._cfg.blackout_poll_s)
        return False

    # distinct from None ("no detector configured"): the detector exists
    # but the plane would not answer — i.e. it was DARK at sample time
    _EPOCH_UNREADABLE = object()

    def _plane_epoch(self) -> Any:
        fn = self._cfg.control_plane_epoch
        if fn is None:
            return None
        try:
            return fn()
        except Exception:  # noqa: BLE001 — unreadable IS a signal
            return self._EPOCH_UNREADABLE

    def _blackout_detected(self, epoch_before: Any) -> bool:
        """A fault round is a control-plane blackout when the plane is
        dark RIGHT NOW, when it was already dark at round START (epoch
        unreadable — the blackout began before the round did), or when
        it restarted during the round (epoch changed — the typed errors
        often surface only once the redial succeeds, i.e. after the
        plane already returned)."""
        if self._cfg.control_plane_probe is None:
            return False
        if not self._control_plane_ok():
            return True
        if epoch_before is None:
            return False  # no restart detector configured
        if epoch_before is self._EPOCH_UNREADABLE:
            return True  # the round was dispatched into a dark plane
        epoch_after = self._plane_epoch()
        if epoch_after is None or epoch_after is self._EPOCH_UNREADABLE:
            return False  # probe says fine but detector flaky: no claim
        return epoch_after != epoch_before

    # -- supervision ----------------------------------------------------------

    def _drive_round(self, step: int, n: int) -> tuple[Optional[list], list, float]:
        """One dispatch of ``n`` steps across the gang. Returns
        (rank0 losses | None on fault, lost worker handles, detect_s)."""
        refs = [w.run_steps.remote(step, n) for w in self._workers]
        by_ref = {id(r): i for i, r in enumerate(refs)}
        pending = set(refs)
        results: dict[int, list] = {}
        faults: dict[int, BaseException] = {}
        # generous outer bound: the collective timeout is the real
        # detector; this only guards a rank wedged OUTSIDE a collective
        deadline = time.monotonic() + n * self._cfg.step_timeout_s + 60.0
        t_fault = None
        wedged: set[int] = set()
        while pending:
            ready, _ = api.wait(list(pending), num_returns=1, timeout=0.2)
            for ref in ready:
                pending.discard(ref)
                rank = by_ref[id(ref)]
                try:
                    results[rank] = api.get(ref)
                except BaseException as e:  # noqa: BLE001
                    faults[rank] = e
                    if t_fault is None:
                        t_fault = time.monotonic()
                        # unblock every survivor still parked in the
                        # broken round NOW — the abort primitive. Best
                        # effort: with the control plane dark the marker
                        # can't publish, and the bounded op timeouts are
                        # the backstop
                        try:
                            abort_collective_group(
                                self._cfg.group_name,
                                f"rank {rank} fault at step {step}: {e!r}",
                            )
                        except Exception:  # noqa: BLE001
                            pass
            if pending and time.monotonic() > deadline:
                try:
                    abort_collective_group(self._cfg.group_name, "round deadline")
                except Exception:  # noqa: BLE001
                    pass
                for ref in pending:
                    rank = by_ref[id(ref)]
                    wedged.add(rank)
                    faults.setdefault(
                        rank,
                        CollectiveTimeoutError(
                            f"rank {rank} never returned from round at "
                            f"step {step}",
                            group=self._cfg.group_name, gen=self._gen,
                            rank=rank,
                        ),
                    )
                break
        if not faults:
            return results[0], [], 0.0
        detect_s = time.monotonic() - t_fault if t_fault is not None else 0.0
        # a rank whose own bounded wait expired is a survivor of a peer's
        # fault — but a rank that never RETURNED by the round deadline is
        # wedged outside the collective plane (e.g. a hung grad_fn) and
        # must be replaced: reusing it would queue the recovery join
        # behind its stuck call
        lost = [
            self._workers[rank]
            for rank, err in faults.items()
            if _classify(err) is not None or rank in wedged
        ]
        self._last_faults = faults
        return None, lost, detect_s

    def fit(self) -> ElasticResult:
        cfg = self._cfg
        state, step = self._restore()
        losses: list = [None] * self._total_steps
        self._spawn_gang(self._world, self._gen, state)
        error: Optional[BaseException] = None
        try:
            while step < self._total_steps:
                n = min(cfg.steps_per_round, self._total_steps - step)
                epoch_before = self._plane_epoch()
                round_losses, lost_workers, detect_s = self._drive_round(step, n)
                if round_losses is not None:
                    for i, lv in enumerate(round_losses):
                        losses[step + i] = lv
                    step += n
                    # checkpoint when this round CROSSED a cadence
                    # boundary (not only when it landed exactly on one —
                    # steps_per_round need not divide checkpoint_every)
                    fetched: Optional[Any] = None
                    if (
                        step // cfg.checkpoint_every
                        > (step - n) // cfg.checkpoint_every
                        or step >= self._total_steps
                    ):
                        state = self._fetch_state()
                        self._save(state, step)
                        fetched = state
                    if self._on_round is not None:
                        state_fn = (
                            (lambda s=fetched: s) if fetched is not None
                            else self._fetch_state
                        )
                        try:
                            self._on_round(step, state_fn)
                        except Exception:  # noqa: BLE001 — hook faults stay downstream
                            logger.warning(
                                "on_round hook failed at step %d", step,
                                exc_info=True,
                            )
                    continue
                # -- recovery -------------------------------------------------
                faults = self._last_faults
                causes = {
                    c for c in (_classify(e) for e in faults.values()) if c
                }
                # no rank actually lost (every fault is a timeout/abort
                # collateral): a peer stalled past the bound or a
                # contribution was dropped — same recovery, full gang
                cause = next(
                    (c for c in ("rank_killed", "rank_died", "partition",
                                 "collective_error") if c in causes),
                    "stall",
                )
                # -- control-plane blackout: wait-and-resume, blame nobody
                if (
                    len(self.blackouts) < cfg.max_blackouts
                    and self._blackout_detected(epoch_before)
                ):
                    from ray_tpu.obs.recorder import span as _span

                    t0 = time.monotonic()
                    with _span("train.blackout", attrs={
                        "group": cfg.group_name, "step": str(step),
                        "gen": str(self._gen),
                    }):
                        logger.warning(
                            "train.blackout: control plane dark at step %d; "
                            "parking (no ranks blamed, budget untouched)",
                            step,
                        )
                        if self._await_control_plane():
                            # every rank survived — re-form the SAME gang
                            # at gen+1 (the aborted round poisoned this
                            # epoch), restore, resume deterministically
                            fault_step = step
                            state, step = self._restore()
                            try:
                                self._spawn_gang(
                                    self._world, self._gen + 1, state,
                                    survivors=list(self._workers),
                                )
                            except BaseException:  # noqa: BLE001
                                self._teardown()
                                self._spawn_gang(
                                    self._world, self._gen + 2, state
                                )
                            self._metrics["blackouts"].inc()
                            rec = Recovery(
                                step=fault_step, resumed_from=step,
                                gen=self._gen, world_size=self._world,
                                ranks_lost=0,
                                cause="control_plane_blackout",
                                detect_s=round(detect_s, 4),
                                recover_s=round(time.monotonic() - t0, 4),
                            )
                            self.blackouts.append(rec)
                            logger.warning(
                                "train.blackout: plane returned after "
                                "%.2fs; resumed from step %d at gen %d",
                                rec.recover_s, step, self._gen,
                            )
                            continue
                    # the plane never came back within blackout_wait_s:
                    # this is a real outage, not a blip — surface it
                    error = next(iter(faults.values()))
                    break
                if len(self.recoveries) >= cfg.max_recoveries:
                    error = next(iter(faults.values()))
                    break
                if len(self.recoveries) >= 2 and all(
                    r.step == step and r.cause == cause
                    for r in self.recoveries[-2:]
                ):
                    # third consecutive IDENTICAL fault trace: batches
                    # are deterministic in (seed, step, rank), so this
                    # is a bug that replays from the checkpoint (e.g. a
                    # grad_fn exception), not pod weather — recovery
                    # cannot fix it; stop instead of burning the rest of
                    # the budget on restore-replay-crash cycles
                    error = next(iter(faults.values()))
                    break
                t0 = time.monotonic()
                from ray_tpu.obs.recorder import span

                with span("train.recovery", attrs={
                    "group": cfg.group_name, "gen": str(self._gen + 1),
                    "cause": cause, "step": str(step),
                    "ranks_lost": str(len(lost_workers)),
                }):
                    survivors = [
                        w for w in self._workers if w not in lost_workers
                    ]
                    for w in lost_workers:
                        try:
                            api.kill(w)
                        except Exception:  # noqa: BLE001
                            pass
                    if cfg.allow_replacement:
                        new_world = self._world
                    else:
                        new_world = max(cfg.min_world_size, len(survivors))
                    if len(survivors) < cfg.min_world_size and not cfg.allow_replacement:
                        error = next(iter(faults.values()))
                        break
                    fault_step = step
                    state, step = self._restore()
                    try:
                        self._spawn_gang(
                            new_world, self._gen + 1, state,
                            survivors=survivors,
                        )
                    except BaseException:  # noqa: BLE001
                        # a survivor died mid-re-form: drop everyone and
                        # build a fresh gang one epoch further on (the
                        # partial gang may have published gen + 1)
                        self._teardown()
                        self._spawn_gang(new_world, self._gen + 2, state)
                    self._metrics["recoveries"].inc()
                    self._metrics["ranks_lost"].inc(float(len(lost_workers)))
                    rec = Recovery(
                        step=fault_step, resumed_from=step, gen=self._gen,
                        world_size=new_world, ranks_lost=len(lost_workers),
                        cause=cause, detect_s=round(detect_s, 4),
                        recover_s=round(time.monotonic() - t0, 4),
                    )
                    self.recoveries.append(rec)
                    logger.warning(
                        "train.recovery: %s at step %d -> gen %d world %d "
                        "(resumed from step %d, %d lost)",
                        cause, rec.step, rec.gen, rec.world_size,
                        rec.resumed_from, rec.ranks_lost,
                    )
            completed = step >= self._total_steps and error is None
            if completed:
                state = self._fetch_state()
            return ElasticResult(
                state=state,
                losses=losses[:step],
                recoveries=list(self.recoveries),
                completed=completed,
                final_gen=self._gen,
                final_world_size=self._world,
                checkpoint=self._manager.latest(),
                error=error,
                blackouts=list(self.blackouts),
            )
        finally:
            self._teardown()
            try:
                destroy_collective_group(cfg.group_name)
            except Exception:  # noqa: BLE001
                pass
