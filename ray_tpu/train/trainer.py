"""JaxTrainer: gang-orchestrated SPMD training.

The DataParallelTrainer equivalent (reference:
python/ray/train/data_parallel_trainer.py:26 DataParallelTrainer →
BackendExecutor _internal/backend_executor.py:69 → WorkerGroup
_internal/worker_group.py:102), built in the Train-v2 controller style
(train/v2/_internal/execution/controller/controller.py:91: a state
machine polling the worker gang, consulting failure policy between
iterations) — with the torch/NCCL bootstrap replaced by the TPU-native
backend: each worker is one host of the gang; `backend_setup` runs
jax.distributed-style bootstrap (on one host: nothing — the mesh IS the
communicator), and in-loop collectives are XLA ops in the user's jitted
step.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from ray_tpu import obs
from ray_tpu.core import api, errors
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import FailureConfig, RunConfig, ScalingConfig
from ray_tpu.train.result import Result
from ray_tpu.train import session as session_mod
from ray_tpu.utils.logging import get_logger

logger = get_logger("ray_tpu.train")


@api.remote(num_cpus=0)
class _ReportChannel:
    """Controller<->gang mailbox. An ACTOR (not a shared Queue/Event) so
    the same trainer drives thread workers in-process AND cluster worker
    processes (reference: session.report travels worker->controller as
    an actor round-trip, train/_internal/session.py:405)."""

    def __init__(self):
        self._reports: list = []
        self._base = 0  # global index of _reports[0]
        self._stop = False

    def put(self, rep: dict) -> bool:
        self._reports.append(rep)
        return self._stop  # piggyback the stop flag on the report ack

    def drain(self, cursor: int = 0) -> list:
        # cursor = number of reports the controller has consumed. Reports
        # at/above the cursor are returned (NOT popped — a timed-out get
        # retries without losing checkpoints); reports below it are acked
        # and pruned so a long run can't grow the channel unboundedly.
        acked = max(0, min(cursor - self._base, len(self._reports)))
        if acked:
            del self._reports[:acked]
            self._base += acked
        return self._reports[max(0, cursor - self._base):]

    def stop(self) -> bool:
        self._stop = True
        return True


class _QueueProxy:
    """Worker-side file of the channel: duck-types queue.put for the
    session; remembers the stop flag the controller piggybacks back."""

    def __init__(self, channel):
        self._channel = channel
        self._stopped = False

    def put(self, rep: dict) -> None:
        ref = self._channel.put.remote(rep)
        self._stopped = bool(api.get(ref))
        try:
            # worker processes BORROW refs (no auto-free); without this a
            # long run leaks one stored ack object per report
            api.free(ref)
        except Exception:
            pass

    def is_set(self) -> bool:  # also serves as the stop_event
        return self._stopped


@api.remote
class _TrainWorker:
    """One gang member (1 per host). Runs the user loop under a session."""

    def __init__(self, rank: int, world_size: int, trial_dir: str, channel):
        proxy = _QueueProxy(channel)
        self.ctx = session_mod.TrainContext(
            world_rank=rank,
            world_size=world_size,
            trial_dir=trial_dir,
            report_queue=proxy,
            stop_event=proxy,
        )

    def reserve_coordinator(self, port=None) -> str:
        """Rank 0 only: pick the jax.distributed coordinator address on
        THIS host (the MASTER_ADDR election of train/torch/config.py:153,
        done via the gang's own worker 0 instead of an env var)."""
        from ray_tpu.parallel.distributed import reserve_coordinator_address

        return reserve_coordinator_address(port=port)

    def setup_distributed(self, coordinator: str, num_processes: int,
                          process_id: int, config) -> bool:
        """Run the jax.distributed bootstrap in this worker process.

        Must happen before the user loop touches a backend; afterwards
        jax.devices() spans the whole gang (reference analog:
        _TorchBackend.on_start, train/torch/config.py:115)."""
        from ray_tpu.parallel.distributed import initialize_gang_member

        initialize_gang_member(coordinator, num_processes, process_id, config)
        return True

    def set_resume_checkpoint(self, ckpt) -> bool:
        self.ctx.latest_checkpoint = ckpt
        return True

    def set_dataset_shards(self, shards: dict) -> bool:
        self.ctx.dataset_shards = shards
        return True

    def run(self, fn: Callable, config: dict,
            fit_started: Optional[float] = None) -> str:
        session_mod._set_session(self.ctx)
        obs.watch_gc()  # a worker process of a cluster never ran init()
        if fit_started is not None:
            # layer span train.worker_start: fit() (or this attempt)
            # began at `fit_started` on the controller; it ends here, as
            # the loop function is entered — placement group, channel,
            # worker actors and their set-up calls
            obs.layer_record("train.worker_start", fit_started,
                             attrs={"rank": self.ctx.world_rank})
        try:
            fn(config) if _wants_arg(fn) else fn()
            return "done"
        except StopIteration:
            return "stopped"
        finally:
            session_mod._clear_session()


def _wants_arg(fn: Callable) -> bool:
    import inspect

    try:
        return len(inspect.signature(fn).parameters) > 0
    except (TypeError, ValueError):
        return True


class JaxTrainer:
    """Run `train_loop_per_worker` on a gang of workers.

    Inside the loop, user code uses ray_tpu.train.session (report /
    get_checkpoint / get_world_rank) and builds its mesh over the host's
    devices (ray_tpu.parallel.make_mesh). For a pod slice, set
    scaling_config.pod_type and the gang maps 1 worker per slice host via
    the slice placement group.
    """

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[dict] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[dict] = None,
        backend_config=None,  # JaxDistributedConfig for multi-host SPMD
    ):
        self._fn = train_loop_per_worker
        self._config = train_loop_config or {}
        self._scaling = scaling_config or ScalingConfig()
        self._run_config = run_config or RunConfig()
        self._datasets = datasets or {}
        self._backend_config = backend_config

    # -- controller ----------------------------------------------------------

    def fit(self) -> Result:
        trial_dir = self._run_config.resolved_storage_path()
        ckpt_cfg = self._run_config.checkpoint_config
        manager = CheckpointManager(
            trial_dir,
            ckpt_cfg.num_to_keep,
            ckpt_cfg.checkpoint_score_attribute,
            ckpt_cfg.checkpoint_score_order,
        )
        failure_cfg = self._run_config.failure_config
        failures = 0
        started = time.time()
        resume_ckpt: Optional[Checkpoint] = None
        # metrics/history accumulate ACROSS attempts (a restart continues the
        # same logical run, reference Train-v2 controller semantics)
        history: list[dict] = []
        last_metrics: dict = {}

        while True:
            try:
                outcome, error = self._run_attempt(
                    trial_dir, manager, resume_ckpt, history, last_metrics,
                    started,
                )
            except BaseException as e:  # noqa: BLE001 - setup failure (e.g. infeasible gang)
                outcome, error = "failed", e
            if outcome == "ok":
                return Result(
                    metrics=dict(last_metrics),
                    checkpoint=manager.latest(),
                    path=trial_dir,
                    metrics_history=history,
                )
            failures += 1
            if failure_cfg.max_failures >= 0 and failures > failure_cfg.max_failures:
                return Result(
                    metrics=dict(last_metrics),
                    checkpoint=manager.latest(),
                    path=trial_dir,
                    error=error,
                    metrics_history=history,
                )
            resume_ckpt = manager.latest()
            started = time.time()  # the next attempt's own start
            logger.warning(
                "train attempt failed (%s); restarting gang (failure %d/%s)",
                error, failures, failure_cfg.max_failures,
            )
            if self._scaling.min_workers:
                # elastic: the failed attempt's leases release asynchronously
                # and the availability view refreshes by heartbeat — POLL for
                # capacity recovery (bounded) instead of guessing a sleep, or
                # the next gang would collapse toward min_workers spuriously
                import time as _time

                # stop early when capacity STABILIZES (two equal readings):
                # a permanently lost node must not cost the full bound on
                # every restart
                deadline = _time.monotonic() + 10.0
                prev = -1
                while _time.monotonic() < deadline:
                    size = self._gang_size()
                    if size >= self._scaling.num_workers or size == prev:
                        break
                    prev = size
                    _time.sleep(0.5)

    def _gang_size(self) -> int:
        """Elastic sizing: the largest gang in [min_workers, num_workers]
        the cluster can place right now (Train-v2 scaling_policy seam)."""
        n = self._scaling.num_workers
        mn = self._scaling.min_workers
        if not mn or mn >= n:
            return n
        req = self._scaling.worker_resources()
        try:
            avail = api.available_resources()
        except Exception:
            return n
        fits = n
        for k, v in req.items():
            if v <= 0:
                continue
            # cluster naming vs in-process naming for the CPU resource
            a = avail.get(k, avail.get("num_cpus" if k == "CPU" else k, 0.0))
            fits = min(fits, int(a // v))
        return max(mn, min(n, fits))

    def _run_attempt(self, trial_dir, manager, resume_ckpt, history, last_metrics,
                     started: float):
        n = self._gang_size()
        if n < self._scaling.num_workers:
            logger.warning(
                "elastic gang: sizing down to %d/%d workers (cluster capacity)",
                n, self._scaling.num_workers,
            )
        channel = None
        cursor = [0]

        def drain():
            if channel is None:
                return
            try:
                reports = api.get(channel.drain.remote(cursor[0]), timeout=30)
            except Exception:
                return  # cursor unchanged: nothing lost, retried next drain
            cursor[0] += len(reports)
            for rep in reports:
                if rep["rank"] == 0:
                    history.append(rep["metrics"])
                    last_metrics.clear()
                    last_metrics.update(rep["metrics"])
                    if rep["checkpoint"] is not None:
                        manager.register(rep["checkpoint"], rep["metrics"])

        bc = self._backend_config
        if (
            bc is not None
            and getattr(bc, "enabled", False)
            and n > 1
            and api._cluster() is None
        ):
            raise errors.RayTpuError(
                "JaxDistributedConfig needs process-isolated workers: "
                "jax.distributed.initialize can run once per process, but the "
                "in-process runtime gangs workers as threads. Attach to a "
                "cluster first: ray_tpu.init(address=...)"
            )

        pg = None
        workers = []
        splitters = []
        try:
            if self._scaling.pod_type:
                from ray_tpu.core.accelerators import parse_pod_type, slice_placement_group

                topo = parse_pod_type(self._scaling.pod_type)
                pg = slice_placement_group(self._scaling.pod_type)
                if not pg.ready(timeout=120):
                    raise errors.PlacementGroupUnavailableError(
                        f"slice {self._scaling.pod_type} unavailable"
                    )
                n = topo.num_hosts
            else:
                res = self._scaling.worker_resources()
                bundles = [dict(res) for _ in range(n)]
                pg = api.placement_group(
                    bundles, strategy=self._scaling.placement_strategy, name="train-gang"
                )
                pg.ready(timeout=120)

            channel = _ReportChannel.remote()
            for rank in range(n):
                strategy = api.PlacementGroupSchedulingStrategy(pg, rank)
                res = self._scaling.worker_resources()
                workers.append(
                    _TrainWorker.options(
                        num_cpus=res.get("CPU", 1.0),
                        num_tpus=res.get("TPU", 0.0),
                        resources={k: v for k, v in res.items() if k not in ("CPU", "TPU")},
                        scheduling_strategy=strategy,
                    ).remote(rank, n, trial_dir, channel)
                )
            if bc is not None and getattr(bc, "enabled", False):
                # gang-wide SPMD bootstrap: rank 0 elects the coordinator,
                # every member runs jax.distributed.initialize
                coordinator = api.get(
                    workers[0].reserve_coordinator.remote(
                        getattr(bc, "coordinator_port", None)
                    ),
                    timeout=60,
                )
                api.get(
                    [
                        w.setup_distributed.remote(coordinator, n, rank, bc)
                        for rank, w in enumerate(workers)
                    ],
                    timeout=300,
                )
            if resume_ckpt is not None:
                api.get([w.set_resume_checkpoint.remote(resume_ckpt) for w in workers])
            if self._datasets:
                # one shared streaming execution per dataset, split across the
                # gang (reference: dataset.py:1598 streaming_split in Train)
                split_map = {
                    name: ds.streaming_split(n) for name, ds in self._datasets.items()
                }
                splitters = [
                    it.splitter for splits in split_map.values() for it in splits[:1]
                ]
                api.get(
                    [
                        w.set_dataset_shards.remote(
                            {name: splits[rank] for name, splits in split_map.items()}
                        )
                        for rank, w in enumerate(workers)
                    ]
                )

            run_refs = [w.run.remote(self._fn, self._config, started)
                        for w in workers]

            pending = set(run_refs)
            while pending:
                drain()
                ready, _ = api.wait(list(pending), num_returns=1, timeout=0.1)
                for ref in ready:
                    pending.discard(ref)
                    api.get(ref)  # raises on worker failure
            drain()
            return "ok", None
        except BaseException as e:  # noqa: BLE001
            if channel is not None:
                try:
                    api.get(channel.stop.remote(), timeout=10)
                except Exception:
                    pass
            drain()  # keep reports/checkpoints that landed before the failure
            return "failed", e
        finally:
            for sp in splitters:
                sp.close()  # unwedge the data pump if a worker died mid-stream
            for w in workers:
                try:
                    api.kill(w)
                except Exception:
                    pass
            if channel is not None:
                try:
                    api.kill(channel)
                except Exception:
                    pass
            if pg is not None:
                try:
                    api.remove_placement_group(pg)
                except Exception:
                    pass
