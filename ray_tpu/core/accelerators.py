"""TPU accelerator management: chips and pod slices as schedulable resources.

Analog of the reference's TPUAcceleratorManager
(python/ray/_private/accelerators/tpu.py:70): detection via environment
and the accelerator device nodes (GKE-style vars; no metadata-server
probe and no backend init here — zero-egress safe, and the caller's
process never takes the chip by asking), `TPU_VISIBLE_CHIPS` isolation
(tpu.py:154), valid per-host chip counts
{1,2,4,8} (tpu.py:14,140-148), and the pod-slice resource pattern
(tpu.py:330-393): every worker of a slice advertises `{slice_name}: 1`
and worker 0 additionally `TPU-{pod_type}-head: 1`, which is the gang-
scheduling hook `slice_run` builds on.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Optional

VALID_CHIPS_PER_HOST = (1, 2, 4, 8)

# chips per host and whether the pod-type number counts cores (2/chip) or chips
_GENERATIONS = {
    "v2": {"chips_per_host": 4, "number_is_cores": True},
    "v3": {"chips_per_host": 4, "number_is_cores": True},
    "v4": {"chips_per_host": 4, "number_is_cores": True},
    "v5p": {"chips_per_host": 4, "number_is_cores": True},
    "v5litepod": {"chips_per_host": 8, "number_is_cores": False},
    "v5e": {"chips_per_host": 8, "number_is_cores": False},
    "v6e": {"chips_per_host": 8, "number_is_cores": False},
}


@dataclasses.dataclass(frozen=True)
class TpuTopology:
    pod_type: str  # e.g. "v5p-16", "v5e-64"
    generation: str
    num_chips: int
    chips_per_host: int

    @property
    def num_hosts(self) -> int:
        return max(1, self.num_chips // self.chips_per_host)

    @property
    def slice_resource_name(self) -> str:
        return f"TPU-{self.pod_type}"

    @property
    def head_resource_name(self) -> str:
        return f"TPU-{self.pod_type}-head"


def parse_pod_type(pod_type: str) -> TpuTopology:
    m = re.fullmatch(r"(v\d+[a-z]*(?:pod)?)-(\d+)", pod_type)
    if not m:
        raise ValueError(f"unparseable TPU pod type {pod_type!r} (want e.g. 'v5p-16')")
    gen, number = m.group(1), int(m.group(2))
    info = _GENERATIONS.get(gen)
    if info is None:
        raise ValueError(f"unknown TPU generation {gen!r} in {pod_type!r}")
    num_chips = number // 2 if info["number_is_cores"] else number
    chips_per_host = min(info["chips_per_host"], max(1, num_chips))
    return TpuTopology(pod_type, gen, num_chips, chips_per_host)


class TpuAcceleratorManager:
    """Per-node TPU detection + isolation. Never opens a backend."""

    @staticmethod
    def device_chip_ids() -> list[int]:
        """Ids of the accelerator device nodes this process can see:
        `/dev/accel<N>` (v2-v4) or `/dev/vfio/<N>` (v5e and newer)."""
        ids = [
            int(p[len("/dev/accel"):]) for p in glob.glob("/dev/accel*")
            if p[len("/dev/accel"):].isdigit()
        ]
        if not ids:
            try:
                ids = [int(n) for n in os.listdir("/dev/vfio") if n.isdigit()]
            except OSError:
                ids = []
        return sorted(ids)

    @classmethod
    def detect_num_chips(cls) -> int:
        """Chips this node should advertise. An explicit statement wins
        (`TPU_VISIBLE_CHIPS` isolation, `RAY_TPU_NUM_CHIPS`); otherwise
        the device nodes are counted — they are what the process can
        actually open — and only a machine that shows none falls back to
        the host topology in `TPU_CHIPS_PER_HOST_BOUNDS`, which describes
        the whole host even inside a container that was given one chip."""
        visible = os.environ.get("TPU_VISIBLE_CHIPS")
        if visible:
            return len([c for c in visible.split(",") if c.strip()])
        explicit = os.environ.get("RAY_TPU_NUM_CHIPS")
        if explicit:
            return int(explicit)
        nodes = cls.device_chip_ids()
        if nodes:
            return len(nodes)
        chips = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")  # e.g. "2,2,1"
        if chips:
            n = 1
            for part in chips.split(","):
                n *= int(part)
            return n
        return 0

    @classmethod
    def node_chip_ids(cls, num_chips: int) -> list[int]:
        """The ids a node advertising `num_chips` hands out to leases:
        its device nodes' own numbers where it has that many, else
        0..num_chips-1 (a node whose count was stated, not detected)."""
        nodes = cls.device_chip_ids()
        return nodes[:num_chips] if len(nodes) >= num_chips else list(range(num_chips))

    @staticmethod
    def detect_pod_type() -> Optional[str]:
        for var in ("TPU_ACCELERATOR_TYPE", "TPU_POD_TYPE"):
            val = os.environ.get(var)
            if val:
                return val
        return None

    @staticmethod
    def detect_worker_id() -> int:
        for var in ("TPU_WORKER_ID", "CLOUD_TPU_TASK_ID"):
            val = os.environ.get(var)
            if val is not None:
                return int(val)
        return 0

    @staticmethod
    def visible_chips_env(chip_ids: list[int], node_chip_ids: list[int]) -> dict:
        """Environment that isolates a worker to `chip_ids` of a node
        holding `node_chip_ids` (reference tpu.py:154-196). A worker
        that owns the whole node gets nothing: the runtime's defaults
        already describe it."""
        if len(chip_ids) not in VALID_CHIPS_PER_HOST:
            raise ValueError(
                f"TPU workers may own {VALID_CHIPS_PER_HOST} chips, not {len(chip_ids)}"
            )
        if sorted(chip_ids) == sorted(node_chip_ids):
            return {}
        env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chip_ids)}
        bounds = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}.get(len(chip_ids))
        if bounds:
            # a sub-host worker is its own one-process "host" of that shape
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        return env

    @classmethod
    def node_resources(cls) -> dict:
        """Resources this node should advertise (chips + slice membership)."""
        out: dict = {}
        chips = cls.detect_num_chips()
        if chips:
            out["TPU"] = float(chips)
        pod_type = cls.detect_pod_type()
        if pod_type:
            topo = parse_pod_type(pod_type)
            out[topo.slice_resource_name] = 1.0
            if cls.detect_worker_id() == 0:
                out[topo.head_resource_name] = 1.0
        return out


def slice_placement_group(pod_type: str, name: str = "", strict: Optional[bool] = None):
    """Reserve one bundle per host of a slice (STRICT_SPREAD over the pod's
    hosts; each bundle pins the host's chips + slice membership).

    strict=None auto-relaxes to SPREAD when the cluster has a single node
    (dev-box simulation of a slice); real multi-host clusters keep the
    one-bundle-per-host guarantee.
    """
    from ray_tpu.core import api, runtime as rt

    topo = parse_pod_type(pod_type)
    bundles = [
        {"TPU": float(topo.chips_per_host), topo.slice_resource_name: 1.0}
        for _ in range(topo.num_hosts)
    ]
    if topo.num_hosts == 1:
        strategy = "STRICT_PACK"
    elif strict is None:
        multi = len(rt.get_runtime().gcs.alive_nodes()) > 1
        strategy = "STRICT_SPREAD" if multi else "SPREAD"
    else:
        strategy = "STRICT_SPREAD" if strict else "SPREAD"
    return api.placement_group(bundles, strategy=strategy, name=name or f"slice-{pod_type}")


def slice_run(fn, pod_type: str, *args, pg=None, **kwargs):
    """Gang-launch `fn(rank, world_size, *args)` on every host of a slice.

    The one-liner version of the reference's documented SPMD pattern
    (tpu.py:356-365: schedule a task per host via the pod-slice resources).
    Returns the list of per-host ObjectRefs (rank order).
    """
    from ray_tpu.core import api

    topo = parse_pod_type(pod_type)
    own_pg = pg is None
    if own_pg:
        pg = slice_placement_group(pod_type)
        if not pg.ready(timeout=60):
            raise TimeoutError(f"slice placement group for {pod_type} not ready")
    remote_fn = api.remote(fn) if not isinstance(fn, api.RemoteFunction) else fn
    refs = []
    for rank in range(topo.num_hosts):
        strategy = api.PlacementGroupSchedulingStrategy(pg, rank)
        refs.append(
            remote_fn.options(
                num_cpus=0,
                num_tpus=float(topo.chips_per_host),
                scheduling_strategy=strategy,
            ).remote(rank, topo.num_hosts, *args, **kwargs)
        )
    return refs
