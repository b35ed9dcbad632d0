"""Multi-process test/deployment cluster harness.

Reference analog: ray.cluster_utils.Cluster (python/ray/cluster_utils.py:135)
— but where round 1's cluster_utils registered capacity rows in an
in-process dict, this spawns a REAL GCS server process and one REAL node
daemon process per node; tasks execute inside worker processes on the
node that won the lease, and killing a node kills an OS process whose
death the GCS detects by heartbeat timeout.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Optional

from ray_tpu.cluster.client import ClusterClient
from ray_tpu.cluster.rpc import format_gcs_addr
from ray_tpu.utils.logging import get_logger

logger = get_logger("ray_tpu.cluster.cluster")


def _read_banner(proc: subprocess.Popen, tag: str, timeout: float = 30.0):
    """Read the '<TAG> host:port ...' line the child prints on startup."""
    result: list = []

    def read():
        assert proc.stdout is not None
        for line in proc.stdout:
            line = line.strip()
            if line.startswith(tag):
                result.append(line.split()[1:])
                break

    t = threading.Thread(target=read, daemon=True)
    t.start()
    t.join(timeout)
    if not result:
        proc.kill()
        raise RuntimeError(f"child did not print {tag} within {timeout}s")
    # keep draining stdout so the child never blocks on a full pipe
    threading.Thread(
        target=lambda: [None for _ in proc.stdout], daemon=True  # type: ignore[union-attr]
    ).start()
    return result[0]


class NodeProc:
    def __init__(self, proc: subprocess.Popen, node_id: str, addr: tuple):
        self.proc = proc
        self.node_id = node_id
        self.addr = addr

    def kill(self) -> None:
        """SIGKILL the daemon AND its workers (the whole node dies).

        A killed daemon can't unlink its tmpfs object-store file (graceful
        stop() does); sweep it here or crash-kill tests leak /dev/shm at
        ~hundreds of MB per run."""
        self._unlink_store()
        try:
            import signal

            os.killpg(os.getpgid(self.proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                self.proc.kill()
            except Exception:
                pass

    def _unlink_store(self) -> None:
        from ray_tpu.utils.shm import shm_dir as _shm_dir

        shm_dir = _shm_dir()
        try:
            os.unlink(os.path.join(
                shm_dir, f"ray_tpu-store-{self.node_id}-{self.proc.pid}"
            ))
        except OSError:
            pass


class LocalCluster:
    """Spawn a GCS + N node-daemon processes on this machine."""

    def __init__(self, node_death_timeout_s: float = 2.0,
                 gcs_persist_path: Optional[str] = None,
                 standby: bool = False,
                 gcs_lease_timeout_s: float = 2.0):
        self._death_timeout = node_death_timeout_s
        self._persist_path = gcs_persist_path
        self._standby_requested = standby
        self._lease_timeout = gcs_lease_timeout_s
        self.gcs_proc: Optional[subprocess.Popen] = None
        self.gcs_addr: Optional[tuple] = None
        self.standby_proc: Optional[subprocess.Popen] = None
        self.standby_addr: Optional[tuple] = None
        self.nodes: dict[str, NodeProc] = {}
        self._client: Optional[ClusterClient] = None
        self._head: Optional[NodeProc] = None

    # -- lifecycle ------------------------------------------------------------

    def _spawn_gcs(self, port: int = 0) -> None:
        cmd = [
            sys.executable, "-m", "ray_tpu.cluster.gcs_service",
            "--death-timeout", str(self._death_timeout),
            "--port", str(port),
        ]
        if self._persist_path:
            cmd += ["--persist", self._persist_path]
        self.gcs_proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, text=True, env=self._child_env(),
            start_new_session=True,
        )
        host_port = _read_banner(self.gcs_proc, "GCS_ADDRESS")[0]
        host, port_s = host_port.rsplit(":", 1)
        self.gcs_addr = (host, int(port_s))

    def _spawn_standby(self) -> None:
        assert self.gcs_addr is not None, "spawn the primary first"
        cmd = [
            sys.executable, "-m", "ray_tpu.cluster.ha",
            "--primary", f"{self.gcs_addr[0]}:{self.gcs_addr[1]}",
            "--death-timeout", str(self._death_timeout),
            "--lease-timeout", str(self._lease_timeout),
            "--port", "0",
        ]
        if self._persist_path:
            cmd += ["--persist", self._persist_path + ".standby"]
        self.standby_proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, text=True, env=self._child_env(),
            start_new_session=True,
        )
        host_port = _read_banner(self.standby_proc, "GCS_ADDRESS")[0]
        host, port_s = host_port.rsplit(":", 1)
        self.standby_addr = (host, int(port_s))

    def start(self) -> "LocalCluster":
        self._spawn_gcs()
        if self._standby_requested:
            self._spawn_standby()
        return self

    @property
    def gcs_endpoints(self) -> tuple:
        """Ordered endpoint list for multi-endpoint clients: primary
        first, standby second (when deployed)."""
        assert self.gcs_addr is not None, "start() first"
        if self.standby_addr is not None:
            return (self.gcs_addr, self.standby_addr)
        return (self.gcs_addr,)

    def kill_gcs(self) -> None:
        """SIGKILL the control plane (FT testing)."""
        if self.gcs_proc is not None:
            try:
                import signal

                os.killpg(os.getpgid(self.gcs_proc.pid), signal.SIGKILL)
            except Exception:
                try:
                    self.gcs_proc.kill()
                except Exception:
                    pass
            self.gcs_proc = None

    def kill_gcs_primary(self) -> None:
        """SIGKILL the primary with NO restart (KILL_GCS_PRIMARY): the
        standby's lease expires and it promotes in place — the failover
        path, as opposed to restart_gcs's blackout-then-replay path."""
        assert self.standby_addr is not None, (
            "kill_gcs_primary requires standby=True"
        )
        self.kill_gcs()

    def restart_gcs(self) -> None:
        """Restart the GCS at the SAME address; with a persist path it
        replays actors/PGs/KV and nodes re-register via heartbeat
        (reference: Redis-backed GCS restart, gcs_init_data.cc)."""
        assert self.gcs_addr is not None, "start() first"
        self.kill_gcs()
        self._spawn_gcs(port=self.gcs_addr[1])

    def _child_env(self, extra: Optional[dict] = None) -> dict:
        from ray_tpu.utils.env import pin_control_plane_to_cpu

        # GCS and daemons never open an accelerator; a daemon gives the
        # chips only to workers whose lease holds TPU
        env = pin_control_plane_to_cpu(dict(os.environ))
        repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.update(extra or {})
        return env

    def add_node(
        self,
        resources: Optional[dict] = None,
        node_id: Optional[str] = None,
        worker_env: Optional[dict] = None,
        object_capacity_bytes: Optional[int] = None,
        worker_rss_limit_mb: Optional[int] = None,
        memory_usage_threshold: Optional[float] = None,
        memory_monitor_interval_s: Optional[float] = None,
    ) -> NodeProc:
        assert self.gcs_addr is not None, "start() first"
        resources = resources or {"num_cpus": 1}
        res_s = ",".join(f"{k}={v}" for k, v in resources.items())
        cmd = [
            sys.executable, "-m", "ray_tpu.cluster.node_daemon",
            "--gcs", format_gcs_addr(self.gcs_endpoints),
            "--resources", res_s,
        ]
        if object_capacity_bytes is not None:
            cmd += ["--object-capacity", str(object_capacity_bytes)]
        if worker_rss_limit_mb is not None:
            cmd += ["--worker-rss-limit-mb", str(worker_rss_limit_mb)]
        # LocalCluster default: DISABLE the machine-wide pressure trigger
        # (dev/CI hosts are shared — an unrelated tenant pushing the box
        # past 95% must not make every test cluster kill its workers);
        # the production `ray start` CLI keeps the raylet-parity 0.95
        cmd += ["--memory-usage-threshold",
                str(1.0 if memory_usage_threshold is None
                    else memory_usage_threshold)]
        if memory_monitor_interval_s is not None:
            cmd += ["--memory-monitor-interval", str(memory_monitor_interval_s)]
        if node_id:
            cmd += ["--node-id", node_id]
        if worker_env:
            cmd += ["--worker-env", ",".join(f"{k}={v}" for k, v in worker_env.items())]
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, text=True, env=self._child_env(),
            start_new_session=True,
        )
        parts = _read_banner(proc, "NODE_ADDRESS")
        host, port = parts[0].rsplit(":", 1)
        node = NodeProc(proc, parts[1], (host, int(port)))
        self.nodes[node.node_id] = node
        if self._head is None:
            self._head = node
        return node

    @property
    def address(self) -> str:
        """GCS address for ray_tpu.init(address=...) — "h:p" or
        "h1:p1,h2:p2" when a standby is deployed."""
        assert self.gcs_addr is not None, "start() first"
        return format_gcs_addr(self.gcs_endpoints)

    def client(self) -> ClusterClient:
        if self._client is None:
            assert self.gcs_addr is not None and self._head is not None
            self._client = ClusterClient(self.gcs_endpoints, self._head.addr)
        return self._client

    def kill_node(self, node_id: str) -> None:
        node = self.nodes.pop(node_id, None)
        if node is not None:
            node.kill()

    def wait_for_nodes(self, n: int, timeout: float = 30.0) -> None:
        c = self.client()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            alive = [x for x in c.nodes() if x["alive"]]
            if len(alive) >= n:
                return
            time.sleep(0.05)
        raise TimeoutError(f"cluster did not reach {n} nodes")

    def wait_node_dead(self, node_id: str, timeout: float = 30.0) -> None:
        c = self.client()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for x in c.nodes():
                if x["node_id"] == node_id and not x["alive"]:
                    return
            time.sleep(0.05)
        raise TimeoutError(f"node {node_id} still alive after {timeout}s")

    def shutdown(self) -> None:
        if self._client is not None:
            self._client.close()
            self._client = None
        for node in list(self.nodes.values()):
            node.kill()
        self.nodes.clear()
        for attr in ("gcs_proc", "standby_proc"):
            proc = getattr(self, attr)
            if proc is not None:
                try:
                    import signal

                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except Exception:
                    try:
                        proc.kill()
                    except Exception:
                        pass
                setattr(self, attr, None)

    def __enter__(self) -> "LocalCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
