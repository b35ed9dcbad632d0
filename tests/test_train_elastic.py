"""Elastic gang training under collective-plane chaos (r12).

Three layers under test:

 1. the collective plane's robustness contract — every op bounded
    (typed ``CollectiveTimeoutError`` instead of a hung allreduce),
    ``abort_collective_group`` wakes blocked survivors immediately, and
    the gang-epoch generation guard turns zombie ranks into
    ``StaleGenerationError`` instead of gradient injectors;
 2. crash-atomic checkpoints — ``.tmp`` staging + rename, partial dirs
    pruned on restore, ``num_to_keep`` never evicting the checkpoint
    currently being restored;
 3. the ``TrainerSupervisor`` loop — detect/abort/re-form/restore/resume
    for every injected fault kind, with same-world-size resume
    loss-IDENTICAL to the uninterrupted run (the determinism contract
    the ``TRAIN_chaos_r12.json`` capture gates in tier-1).
"""

import json
import os
import pickle
import threading
import time

import numpy as np
import pytest

from ray_tpu.chaos import (
    DROP_COLLECTIVE,
    KILL_RANK,
    PARTIAL_PARTITION,
    STALL_COLLECTIVE,
    FaultSchedule,
    FaultSpec,
    install,
    uninstall,
)
from ray_tpu.collective import (
    CollectiveAbortedError,
    CollectiveTimeoutError,
    StaleGenerationError,
    abort_collective_group,
    allreduce,
    destroy_collective_group,
    get_gang_epoch,
    init_collective_group,
)
from ray_tpu.train.checkpoint import (
    Checkpoint,
    CheckpointManager,
    is_complete,
    latest_complete,
    prune_partial,
)
from ray_tpu.train.elastic import (
    ElasticConfig,
    TrainerSupervisor,
    register_metrics,
    rng_for,
)

pytestmark = pytest.mark.train_chaos


# -- toy deterministic problem (linear regression, pure numpy) ---------------

W_TRUE = np.asarray([1.0, -2.0, 3.0, 0.5])


def init_fn(seed):
    return {"w": np.zeros(4, np.float64)}


def grad_fn(state, batch):
    x, y = batch
    err = x @ state["w"] - y
    return float(np.mean(err ** 2)), {"w": 2 * x.T @ err / len(y)}


def apply_fn(state, grads):
    return {"w": state["w"] - 0.1 * grads["w"]}


def batch_fn(seed, step, world, rank):
    rng = rng_for(seed, step, rank)
    x = rng.normal(size=(8, 4))
    return x, x @ W_TRUE


def _fit(root, total_steps=12, spec=None, schedule_seed=7, **cfg_kw):
    cfg = ElasticConfig(
        world_size=2, step_timeout_s=3.0, checkpoint_every=4,
        sharded_checkpoints=False, **cfg_kw,
    )
    if spec is not None:
        specs = spec if isinstance(spec, list) else [spec]
        install(FaultSchedule(schedule_seed, specs))
    try:
        sup = TrainerSupervisor(
            init_fn=init_fn, grad_fn=grad_fn, apply_fn=apply_fn,
            batch_fn=batch_fn, total_steps=total_steps,
            checkpoint_root=root, config=cfg,
        )
        return sup.fit()
    finally:
        if spec is not None:
            uninstall()


# -- collective plane --------------------------------------------------------


def test_bounded_rendezvous_raises_typed_timeout():
    """A peer that never arrives surfaces as CollectiveTimeoutError
    within the bound — the no-hung-allreduce contract."""
    init_collective_group(2, 0, group_name="t_bound")
    try:
        t0 = time.monotonic()
        with pytest.raises(CollectiveTimeoutError) as ei:
            allreduce(np.ones(2), group_name="t_bound", rank=0, timeout=0.5)
        assert time.monotonic() - t0 < 5.0
        assert ei.value.group == "t_bound"
        # legacy callers that catch TimeoutError keep working
        assert isinstance(ei.value, TimeoutError)
    finally:
        destroy_collective_group("t_bound")


def test_abort_wakes_blocked_waiter_immediately():
    """abort_collective_group unblocks a parked rank well before its
    timeout — the supervisor's abort-the-step primitive."""
    init_collective_group(2, 0, group_name="t_abort")
    errs = {}

    def waiter():
        try:
            allreduce(np.ones(2), group_name="t_abort", rank=0, timeout=30.0)
        except BaseException as e:  # noqa: BLE001
            errs["rank0"] = e

    th = threading.Thread(target=waiter, daemon=True)
    th.start()
    time.sleep(0.3)
    t0 = time.monotonic()
    abort_collective_group("t_abort", "test abort")
    th.join(timeout=5.0)
    assert not th.is_alive()
    assert time.monotonic() - t0 < 2.0  # woke on abort, not on timeout
    assert isinstance(errs["rank0"], CollectiveAbortedError)
    destroy_collective_group("t_abort")


def test_generation_guard_refuses_zombie_rank():
    """Re-forming the same group at gen+1 supersedes the old incarnation:
    a zombie rank of the old gen gets StaleGenerationError (its wait is
    woken, its future ops refused) — it can never inject into the new
    gang."""
    init_collective_group(2, 0, group_name="t_gen", gen=0)
    errs = {}

    def zombie():
        try:
            allreduce(np.full(2, 666.0), group_name="t_gen", rank=0,
                      timeout=30.0)
        except BaseException as e:  # noqa: BLE001
            errs["zombie"] = e

    th = threading.Thread(target=zombie, daemon=True)
    th.start()
    time.sleep(0.3)
    # supervisor re-forms at gen 1 (one-rank gang)
    init_collective_group(1, 0, group_name="t_gen", gen=1)
    th.join(timeout=5.0)
    assert not th.is_alive()
    assert isinstance(errs["zombie"], (CollectiveAbortedError,
                                       StaleGenerationError))
    assert get_gang_epoch("t_gen") == 1
    # joining below the current epoch is refused outright
    with pytest.raises(StaleGenerationError):
        init_collective_group(2, 0, group_name="t_gen", gen=0)
    # the new gang computes from its own ranks only
    out = allreduce(np.ones(2), group_name="t_gen", rank=0, timeout=5.0)
    np.testing.assert_allclose(out, np.ones(2))
    destroy_collective_group("t_gen")


def test_drop_collective_not_burned_at_recv():
    """DROP_COLLECTIVE only fires at ops that contribute data: a recv
    has nothing in flight to lose, so a max_fires=1 spec must keep its
    budget through recv and land on the next send/rendezvous (fire()'s
    site-kind contract)."""
    from ray_tpu.collective.collective import collective_chaos

    spec = FaultSpec(kind=DROP_COLLECTIVE, site="collective.rendezvous",
                     p=1.0, max_fires=1)
    install(FaultSchedule(11, [spec]))
    try:
        assert collective_chaos("t_drop", 0, 0, "recv") is False
        assert collective_chaos("t_drop", 0, 0, "send") is True  # budget intact
        assert collective_chaos("t_drop", 0, 0, "send") is False  # now spent
    finally:
        uninstall()


def test_driver_declared_group_cleans_cluster_kv(monkeypatch):
    """A supervisor whose ranks join from their own processes never
    holds a local group object — declare_collective_group must route its
    destroy to the GCS KV cleanup (a leaked gen key would poison the
    next run reusing the group name)."""
    from ray_tpu.collective import declare_collective_group
    from ray_tpu.collective import collective as coll
    from ray_tpu.cluster import client as cl
    from ray_tpu.collective import cluster_group as cg

    cleared = []
    monkeypatch.setattr(cl, "_ambient_client", lambda: object())
    monkeypatch.setattr(
        cg, "clear_group_kv", lambda client, name: cleared.append(name)
    )
    declare_collective_group(2, "cluster", "t_decl")
    assert coll._declared["t_decl"]["backend"] == "cluster"
    destroy_collective_group("t_decl")
    assert cleared == ["t_decl"]
    assert "t_decl" not in coll._declared


def test_fetch_state_survives_dead_rank(tmp_path):
    """Every rank ends every step with identical state, so the
    checkpoint fetch falls back past a rank that died AFTER the round —
    that death is detected at the next dispatch, not here."""
    from ray_tpu.core import api
    from ray_tpu.train.elastic import _ElasticRank

    sup = TrainerSupervisor(
        init_fn=init_fn, grad_fn=grad_fn, apply_fn=apply_fn,
        batch_fn=batch_fn, total_steps=1, checkpoint_root=str(tmp_path),
        config=ElasticConfig(world_size=2, sharded_checkpoints=False),
    )
    ranks = [
        _ElasticRank.remote(grad_fn, apply_fn, batch_fn, 0,
                            "t_fetch", 3.0, "host")
        for _ in range(2)
    ]
    api.get([r.set_state.remote({"w": np.full(4, float(i))})
             for i, r in enumerate(ranks)], timeout=30)
    api.kill(ranks[0])
    sup._workers = ranks
    state = sup._fetch_state()
    assert np.array_equal(state["w"], np.full(4, 1.0))
    api.kill(ranks[1])


def test_old_swap_residue_recovered(tmp_path):
    """A crash between _swap_into_place's renames leaves the previous
    good checkpoint aside as .old — restore renames it back instead of
    losing both."""
    d = tmp_path / "checkpoint_000001"
    Checkpoint.from_state({"w": 7}, str(d))
    os.rename(str(d), str(d) + ".old")  # crashed mid-swap: base missing
    ck = latest_complete(str(tmp_path))
    assert ck is not None
    assert ck.load_state() == {"w": 7}
    assert not os.path.exists(str(d) + ".old")
    # retry-over-orphan: dest missing, .old the ONLY complete copy — a
    # new save to the same dest must leave .old untouched until the new
    # dir is installed (never a window holding only a .tmp)
    os.rename(str(d), str(d) + ".old")
    Checkpoint.from_state({"w": 9}, str(d))
    assert Checkpoint(str(d)).load_state() == {"w": 9}
    assert not os.path.exists(str(d) + ".old")


def test_deterministic_bug_fails_fast(tmp_path):
    """A grad_fn bug replays identically from the checkpoint (batches
    are pure in (seed, step, rank)): after the third identical fault
    trace the supervisor stops instead of burning max_recoveries on
    restore-replay-crash cycles."""
    from ray_tpu.obs.recorder import get_recorder

    def bad_grad(state, batch):
        raise ZeroDivisionError("user bug, deterministic")

    cfg = ElasticConfig(world_size=2, step_timeout_s=3.0,
                        checkpoint_every=4, sharded_checkpoints=False)
    sup = TrainerSupervisor(
        init_fn=init_fn, grad_fn=bad_grad, apply_fn=apply_fn,
        batch_fn=batch_fn, total_steps=12,
        checkpoint_root=str(tmp_path), config=cfg,
    )
    try:
        res = sup.fit()
    finally:
        # this run's rank_died recovery spans must not pollute the
        # process-global flight recorder other tests assert over
        get_recorder().clear()
    assert not res.completed
    assert res.error is not None
    assert len(res.recoveries) == 2  # two replays, then fail fast < 8


def test_chaos_same_seed_same_faults(tmp_path):
    """Seeded schedules are deterministic end-to-end through the trainer:
    same seed => same fault sequence => same recovery trace."""
    spec = FaultSpec(kind=KILL_RANK, site="collective.rendezvous", p=0.5,
                     max_fires=2, match={"rank": "1"})
    traces = []
    for run in range(2):
        res = _fit(str(tmp_path / f"run{run}"), spec=spec, schedule_seed=3)
        assert res.completed
        traces.append([(r.step, r.cause, r.ranks_lost) for r in res.recoveries])
    assert traces[0] == traces[1]


# -- crash-atomic checkpoints ------------------------------------------------


def test_checkpoint_save_is_crash_atomic(tmp_path):
    """A kill mid-save leaves only .tmp residue; restore prunes it and
    never loads a partial checkpoint."""
    root = str(tmp_path)
    good = os.path.join(root, "checkpoint_000000")
    Checkpoint.from_state({"w": np.arange(3.0), "step": 4}, good)
    assert is_complete(good)

    # simulate a rank killed mid-save: staged .tmp dir, half-written
    partial = os.path.join(root, "checkpoint_000001" + ".tmp")
    os.makedirs(partial)
    with open(os.path.join(partial, "garbage"), "wb") as f:
        f.write(b"torn")
    # and a renamed-but-payload-less dir (e.g. crashed between mkdir
    # and write in a pre-r12 layout)
    empty = os.path.join(root, "checkpoint_000002")
    os.makedirs(empty)

    latest = latest_complete(root)
    assert latest is not None and latest.path == good
    assert not os.path.exists(partial)   # pruned
    assert not os.path.exists(empty)     # pruned
    state = latest.load_state()
    np.testing.assert_allclose(state["w"], np.arange(3.0))


def test_checkpoint_pruning_pins_restoring(tmp_path):
    """num_to_keep eviction must never delete the checkpoint a restore
    is currently reading."""
    mgr = CheckpointManager(str(tmp_path), num_to_keep=2)
    ckpts = []
    for i in range(2):
        c = Checkpoint.from_state({"step": i}, mgr.new_checkpoint_dir())
        mgr.register(c)
        ckpts.append(c)
    oldest = ckpts[0]
    with mgr.restoring(oldest):
        # two more registrations would normally evict `oldest` —
        # the pin defers it
        for i in range(2, 4):
            c = Checkpoint.from_state({"step": i}, mgr.new_checkpoint_dir())
            mgr.register(c)
            assert os.path.isdir(oldest.path)
        assert oldest.load_state()["step"] == 0  # still fully readable
    # unpinned: the next registration may evict it
    c = Checkpoint.from_state({"step": 4}, mgr.new_checkpoint_dir())
    mgr.register(c)
    assert not os.path.isdir(oldest.path)
    assert mgr.latest().load_state()["step"] == 4


@pytest.mark.parametrize("kw, accs, evicted, best", [
    ({"score_attribute": "acc", "score_order": "max"}, [0.5, 0.1, 0.3, 0.4], [1, 2], 0),
    ({"score_attribute": "acc", "score_order": "min"}, [0.5, 0.1, 0.3, 0.2], [0, 2], 1),
    ({}, [0.5, 0.1, 0.3, 0.4], [0, 1], None),
], ids=["max", "min", "no_score_attribute"])
def test_checkpoint_eviction_by_score(tmp_path, kw, accs, evicted, best):
    """num_to_keep drops the worst score (with no score_attribute the
    oldest), never reordering: latest() stays the newest."""
    mgr = CheckpointManager(str(tmp_path), num_to_keep=2, **kw)
    ckpts = []
    for step, acc in enumerate(accs):
        c = Checkpoint.from_state({"step": step}, mgr.new_checkpoint_dir())
        mgr.register(c, {"acc": acc})
        ckpts.append(c)
        assert mgr.latest() is c
    assert [i for i, c in enumerate(ckpts) if not os.path.isdir(c.path)] == evicted
    assert mgr.latest().load_state()["step"] == 3
    if best is not None:
        assert mgr.best() is ckpts[best]


def test_prune_partial_only_touches_residue(tmp_path):
    root = str(tmp_path)
    good = os.path.join(root, "checkpoint_000000")
    Checkpoint.from_state({"x": 1}, good)
    os.makedirs(os.path.join(root, "checkpoint_000001.tmp"))
    with open(os.path.join(root, "notes.txt"), "w") as f:
        f.write("keep me")
    pruned = prune_partial(root)
    assert pruned == [os.path.join(root, "checkpoint_000001.tmp")]
    assert os.path.isdir(good)
    assert os.path.isfile(os.path.join(root, "notes.txt"))


# -- supervisor recovery -----------------------------------------------------


def test_uninterrupted_run_is_deterministic(tmp_path):
    r1 = _fit(str(tmp_path / "a"))
    r2 = _fit(str(tmp_path / "b"))
    assert r1.completed and r2.completed
    assert r1.losses == r2.losses
    assert r1.recoveries == [] and r2.recoveries == []


@pytest.mark.parametrize("kind,extra,expect_cause", [
    (KILL_RANK, {}, "rank_killed"),
    (PARTIAL_PARTITION, {}, "partition"),
    (STALL_COLLECTIVE, {"delay_s": 5.0}, "stall"),
    (DROP_COLLECTIVE, {}, "stall"),
])
def test_recovery_is_loss_identical(tmp_path, kind, extra, expect_cause):
    """Every injected fault kind: the gang recovers (>=1 recovery),
    completes all steps, and the per-step losses are BITWISE identical
    to the uninterrupted run — the deterministic-resume contract."""
    base = _fit(str(tmp_path / "base"))
    spec = FaultSpec(kind=kind, site="collective.rendezvous", p=1.0,
                     max_fires=1, start_after=6, match={"rank": "1"}, **extra)
    res = _fit(str(tmp_path / "chaos"), spec=spec)
    assert res.completed
    assert len(res.recoveries) == 1
    assert res.recoveries[0].cause == expect_cause
    assert res.final_world_size == 2  # replacement, not shrink
    assert res.losses == base.losses  # loss-identical resume


def test_elastic_shrink_when_replacement_disallowed(tmp_path):
    """allow_replacement=False: the gang shrinks toward min_world_size
    and still completes (losses legitimately differ after the shrink —
    fewer shards per step — but training finishes)."""
    spec = FaultSpec(kind=KILL_RANK, site="collective.rendezvous", p=1.0,
                     max_fires=1, start_after=6, match={"rank": "1"})
    res = _fit(str(tmp_path), spec=spec, allow_replacement=False,
               min_world_size=1)
    assert res.completed
    assert len(res.recoveries) == 1
    assert res.final_world_size == 1
    assert res.recoveries[0].world_size == 1
    assert len(res.losses) == 12


def test_recovery_budget_exhaustion_surfaces_error(tmp_path):
    """An unbounded fault storm must not loop forever: after
    max_recoveries the supervisor returns completed=False with the
    last fault as the error."""
    spec = FaultSpec(kind=KILL_RANK, site="collective.rendezvous", p=1.0,
                     match={"rank": "1"})  # fires EVERY step, forever
    res = _fit(str(tmp_path), spec=spec, max_recoveries=2)
    assert not res.completed
    assert res.error is not None
    assert len(res.recoveries) == 2


def test_recovery_observability(tmp_path):
    """Recoveries move the ray_tpu_train_* metrics and leave a
    train.recovery span in the flight recorder."""
    from ray_tpu.obs.recorder import get_recorder

    metrics = register_metrics()

    def _read(name):
        return metrics[name].series().get((), 0.0)

    rec0 = _read("recoveries")
    lost0 = _read("ranks_lost")
    spec = FaultSpec(kind=KILL_RANK, site="collective.rendezvous", p=1.0,
                     max_fires=1, start_after=6, match={"rank": "1"})
    res = _fit(str(tmp_path), spec=spec)
    assert res.completed and len(res.recoveries) == 1
    assert _read("gang_epoch") >= 1.0
    assert _read("recoveries") == rec0 + 1
    assert _read("ranks_lost") == lost0 + 1
    rec = get_recorder()
    all_spans = [
        s for m in rec.traces(limit=1000) for s in rec.get(m["trace_id"])
    ]
    spans = [s for s in all_spans if s.name == "train.recovery"]
    assert spans, "train.recovery span must be recorded"
    attrs = spans[-1].attrs
    assert attrs["cause"] == "rank_killed"
    assert attrs["ranks_lost"] == "1"
    # the chaos event itself is mirrored too (post-mortem trail)
    assert any(s.name == "chaos.kill_rank" for s in all_spans)


def test_trainer_health_in_status(tmp_path):
    """The trainer metrics ride the r11 telemetry plane: a snapshot of
    this process's registry after a recovery, ingested into a
    TelemetryStore, surfaces gang epoch / recoveries in status_payload
    and the rendered `ray_tpu status` output."""
    from ray_tpu.obs.telemetry import TelemetryStore, format_status
    from ray_tpu.util.metrics import snapshot_registry

    register_metrics()
    spec = FaultSpec(kind=KILL_RANK, site="collective.rendezvous", p=1.0,
                     max_fires=1, start_after=6, match={"rank": "1"})
    res = _fit(str(tmp_path), spec=spec)
    assert res.completed and len(res.recoveries) == 1

    store = TelemetryStore()
    store.ingest("trainer-host", snapshot_registry())
    payload = store.status_payload()
    trainer = payload["trainer"]
    assert trainer["gang_epoch"] is not None and trainer["gang_epoch"] >= 1
    assert trainer["recoveries_total"] >= 1
    assert trainer["ranks_lost_total"] >= 1
    text = format_status(payload)
    assert "== trainer ==" in text
    assert "gang epoch" in text


def test_resume_from_cold_checkpoint(tmp_path):
    """A brand-new supervisor over the same checkpoint root resumes from
    the last complete checkpoint, not step 0 — and its continuation is
    loss-identical to the uninterrupted run's tail."""
    root = str(tmp_path)
    base = _fit(root + "/base", total_steps=12)
    # run 8 of 12 steps, then "lose the driver"
    r1 = _fit(root + "/resume", total_steps=8)
    assert r1.completed
    # cold resume: new supervisor, same root, full horizon
    r2 = _fit(root + "/resume", total_steps=12)
    assert r2.completed
    # steps 8..11 match the uninterrupted run exactly
    assert r2.losses[8:] == base.losses[8:]


# -- tier-1 capture gate -----------------------------------------------------

_CAPTURE = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "TRAIN_chaos_r12.json")


def test_train_chaos_capture_gate():
    """The checked-in bench capture must show the acceptance bar:
    completion 1.0 under seeded KILL_RANK + PARTIAL_PARTITION, >=1
    recovery, and same-world-size resume loss-identical to the
    uninterrupted run."""
    with open(_CAPTURE) as f:
        cap = json.load(f)
    chaos = cap["chaos"]
    assert chaos["completion_rate"] == 1.0
    assert chaos["recoveries"] >= 1
    assert chaos["loss_identical"] is True
    assert chaos["max_abs_loss_diff"] == 0.0
    kinds = {f["kind"] for f in cap["faults_fired"]}
    assert {"kill_rank", "partial_partition"} <= kinds
    assert cap["config"]["world_size"] == cap["chaos"]["final_world_size"]


@pytest.mark.slow
def test_train_chaos_bench_smoke(tmp_path):
    """The bench itself runs end-to-end on CPU and reproduces the gated
    invariants (no capture overwrite)."""
    import subprocess
    import sys

    out = str(tmp_path / "cap.json")
    r = subprocess.run(
        [sys.executable, "benchmarks/train_chaos_bench.py", "--steps", "16",
         "--out", out],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        cap = json.load(f)
    assert cap["chaos"]["completion_rate"] == 1.0
    assert cap["chaos"]["loss_identical"] is True
    assert cap["chaos"]["recoveries"] >= 1
