"""In-worker training session (analog of reference
python/ray/train/_internal/session.py: report:405, get_context).

Worker code calls `session.report(metrics, checkpoint=...)`; the
controller consumes reports between polls. Thread-local so concurrent
trainer workers in one host process don't collide.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Optional

from ray_tpu import obs
from ray_tpu.train.checkpoint import Checkpoint

_local = threading.local()


@dataclasses.dataclass
class TrainContext:
    world_rank: int
    world_size: int
    trial_dir: str
    report_queue: Any  # queue.Queue shared with the controller
    latest_checkpoint: Optional[Checkpoint] = None
    group_name: str = "train"
    stop_event: Optional[threading.Event] = None
    dataset_shards: dict = dataclasses.field(default_factory=dict)


def _set_session(ctx: TrainContext) -> None:
    _local.ctx = ctx


def _clear_session() -> None:
    _local.ctx = None


def get_context() -> TrainContext:
    ctx = getattr(_local, "ctx", None)
    if ctx is None:
        raise RuntimeError("not inside a train worker (no active session)")
    return ctx


def get_world_rank() -> int:
    return get_context().world_rank


def get_world_size() -> int:
    return get_context().world_size


def get_trial_dir() -> str:
    return get_context().trial_dir


def get_checkpoint() -> Optional[Checkpoint]:
    """Checkpoint to resume from (set after a failure restart)."""
    return get_context().latest_checkpoint


def get_dataset_shard(name: str = "train"):
    """This worker's split of a Dataset passed to JaxTrainer(datasets=...)
    (reference: ray.train.get_dataset_shard backed by streaming_split).
    One streaming pass per attempt; re-create the trainer run for epochs
    beyond the pipeline's output."""
    shards = get_context().dataset_shards
    if name not in shards:
        raise KeyError(
            f"no dataset shard {name!r}; pass datasets={{'{name}': ds}} to the trainer"
        )
    return shards[name]


def report(metrics: dict, checkpoint: Optional[Checkpoint] = None) -> None:
    ctx = get_context()
    # layer span train.report: what one report costs the worker's loop (an
    # actor round trip to the controller's mailbox); chipbench's
    # report_ms.train reads it from the profiler's trace
    with obs.layer_span("train.report"):
        ctx.report_queue.put(
            {"rank": ctx.world_rank, "metrics": dict(metrics),
             "checkpoint": checkpoint, "ts": time.time()}
        )
    if ctx.stop_event is not None and ctx.stop_event.is_set():
        raise StopIteration("controller requested stop")
