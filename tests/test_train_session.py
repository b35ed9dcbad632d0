"""train/session.py and train/config.py with no cluster: the worker-side
session every train cell reports through, the resource and path rules of
the trainer's configs, and the shape of the train step's entry points."""

import inspect
import os
import queue
import tempfile
import threading
import time

import optax
import pytest

from ray_tpu import obs
from ray_tpu.train import session
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.session import TrainContext
from ray_tpu.train.step import make_train_step
from ray_tpu.train.trainer import JaxTrainer


@pytest.fixture
def ctx():
    c = TrainContext(world_rank=3, world_size=4, trial_dir="/nowhere",
                     report_queue=queue.Queue(), stop_event=threading.Event())
    session._set_session(c)
    yield c
    session._clear_session()


def test_get_context_outside_a_worker_raises():
    session._clear_session()
    with pytest.raises(RuntimeError, match="not inside a train worker"):
        session.get_context()
    with pytest.raises(RuntimeError):
        session.report({"loss": 1.0})


def test_report_enqueues_a_copy_with_rank_and_timestamp(ctx):
    metrics = {"loss": 2.5}
    t0 = time.time()
    session.report(metrics)
    metrics["loss"] = -1.0  # the loop reuses its dict: the report must not see it
    rep = ctx.report_queue.get_nowait()
    assert rep["metrics"] == {"loss": 2.5} and rep["rank"] == 3
    assert rep["checkpoint"] is None and t0 <= rep["ts"] <= time.time()
    assert session.get_world_rank() == 3 and session.get_world_size() == 4
    assert session.get_trial_dir() == "/nowhere" and session.get_checkpoint() is None


def test_report_stops_the_loop_after_the_report_is_enqueued(ctx):
    ctx.stop_event.set()
    with pytest.raises(StopIteration):
        session.report({"step": 7})
    # the controller still gets the last report of a stopped loop
    assert ctx.report_queue.get_nowait()["metrics"] == {"step": 7}


def test_get_dataset_shard_names_the_missing_shard(ctx):
    ctx.dataset_shards["train"] = shard = object()
    assert session.get_dataset_shard() is shard
    with pytest.raises(KeyError, match="no dataset shard 'eval'.*datasets=\\{'eval': ds\\}"):
        session.get_dataset_shard("eval")


def test_two_threads_hold_two_sessions(ctx):
    seen = {}

    def worker(rank):
        try:
            session.get_context()
        except RuntimeError:
            seen[rank, "fresh"] = True  # a new thread inherits no session
        mine = TrainContext(world_rank=rank, world_size=2, trial_dir="",
                            report_queue=queue.Queue())
        session._set_session(mine)
        session.report({"from": rank})
        seen[rank] = mine.report_queue.get_nowait()["rank"]

    threads = [threading.Thread(target=worker, args=(r,)) for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20)
    assert seen == {0: 0, 1: 1, (0, "fresh"): True, (1, "fresh"): True}
    # neither touched this thread's session nor its queue
    assert session.get_world_rank() == 3 and ctx.report_queue.empty()


def test_one_report_is_one_train_report_layer_span(ctx):
    before = obs.layer_counters().get("train.report", {"count": 0})["count"]
    session.report({"loss": 0.0})
    assert obs.layer_counters()["train.report"]["count"] == before + 1


@pytest.mark.parametrize("kw, want", [
    ({}, {"CPU": 1.0}),
    ({"use_tpu": True, "chips_per_worker": 4}, {"CPU": 1.0, "TPU": 4.0}),
    ({"use_tpu": True, "chips_per_worker": 0}, {"CPU": 1.0}),
    ({"resources_per_worker": {"CPU": 8.0, "host": 1.0}}, {"CPU": 8.0, "host": 1.0}),
], ids=["default", "tpu_with_chips", "tpu_with_0_chips", "users_cpu_kept"])
def test_scaling_config_worker_resources(kw, want):
    cfg = ScalingConfig(**kw)
    assert cfg.worker_resources() == want
    # a fresh dict each call: the trainer edits the bundles it builds from it
    assert cfg.worker_resources() is not cfg.resources_per_worker


@pytest.mark.parametrize("kw, want", [
    ({}, os.path.join(tempfile.gettempdir(), "ray_tpu_results", "train_run")),
    ({"name": "m7b", "storage_path": "/data/runs"}, "/data/runs/m7b"),
], ids=["default", "named"])
def test_run_config_resolved_storage_path(kw, want):
    assert RunConfig(**kw).resolved_storage_path() == want


def test_the_train_step_is_the_jit_object_and_nothing_offers_profile():
    step = make_train_step(lambda p, b: (p["w"] * b).sum(), optax.sgd(0.1))
    # the jax.jit object itself: the benchmark lowers it, donation and all
    assert callable(step.lower) and not hasattr(step, "profile")
    for fn in (make_train_step, JaxTrainer.__init__):
        assert "profile" not in inspect.signature(fn).parameters, fn
    with pytest.raises(TypeError):
        make_train_step(lambda p, b: 0.0, optax.sgd(0.1), profile=True)
    with pytest.raises(TypeError):
        JaxTrainer(lambda: None, profile=True)
    assert not hasattr(session, "profiling_enabled")
    assert "profile" not in TrainContext.__dataclass_fields__
