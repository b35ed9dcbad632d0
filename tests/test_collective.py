"""Collective API + TPU slice resource tests (modeled on reference
python/ray/util/collective/tests/ and python/ray/tests/accelerators/)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import collective as col
from ray_tpu.core import runtime as rt
from ray_tpu.core.accelerators import (
    TpuAcceleratorManager,
    parse_pod_type,
    slice_placement_group,
    slice_run,
)


@pytest.fixture
def ray_start():
    if rt.is_initialized():
        rt.shutdown_runtime()
    ray_tpu.init(num_cpus=8)
    yield
    rt.shutdown_runtime()
    col.destroy_collective_group("g")


def test_parse_pod_types():
    t = parse_pod_type("v5p-16")
    assert t.num_chips == 8 and t.chips_per_host == 4 and t.num_hosts == 2
    t = parse_pod_type("v5e-16")
    assert t.num_chips == 16 and t.chips_per_host == 8 and t.num_hosts == 2
    t = parse_pod_type("v4-8")
    assert t.num_chips == 4 and t.num_hosts == 1
    with pytest.raises(ValueError):
        parse_pod_type("gpu-8")


def test_node_resources_pattern(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5p-16")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    res = TpuAcceleratorManager.node_resources()
    assert res == {"TPU": 4.0, "TPU-v5p-16": 1.0, "TPU-v5p-16-head": 1.0}
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    res = TpuAcceleratorManager.node_resources()
    assert "TPU-v5p-16-head" not in res


def test_chip_detection_counts_device_nodes(monkeypatch):
    """No env var says how many chips: count the accelerator device
    nodes (never a backend). The host-topology variable describes the
    whole host even in a container that was given one chip (seen on the
    one-chip v5e machine: bounds 2,2,1 beside a single /dev/vfio/2)."""
    for var in ("TPU_VISIBLE_CHIPS", "RAY_TPU_NUM_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(TpuAcceleratorManager, "device_chip_ids", staticmethod(lambda: []))
    assert TpuAcceleratorManager.detect_num_chips() == 0
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    assert TpuAcceleratorManager.detect_num_chips() == 4  # nothing better known
    monkeypatch.setattr(TpuAcceleratorManager, "device_chip_ids", staticmethod(lambda: [2]))
    assert TpuAcceleratorManager.detect_num_chips() == 1
    assert TpuAcceleratorManager.node_chip_ids(1) == [2]
    assert TpuAcceleratorManager.node_chip_ids(4) == [0, 1, 2, 3]  # stated, not detected
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "8")
    assert TpuAcceleratorManager.detect_num_chips() == 8
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
    assert TpuAcceleratorManager.detect_num_chips() == 2


def test_visible_chips_env():
    env = TpuAcceleratorManager.visible_chips_env
    assert env([0, 1, 2, 3], [0, 1, 2, 3]) == {}  # the whole node: defaults
    assert env([2], [2]) == {}
    assert env([3], [0, 1, 2, 3]) == {
        "TPU_VISIBLE_CHIPS": "3", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1",
    }
    assert env([0, 1], [0, 1, 2, 3])["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    with pytest.raises(ValueError):
        env([0, 1, 2], [0, 1, 2, 3])


def test_collective_allreduce_actors(ray_start):
    @ray_tpu.remote
    class Worker:
        def __init__(self, rank, world):
            col.init_collective_group(world, rank, group_name="g")
            self.rank = rank

        def step(self):
            out = col.allreduce(np.ones(4) * (self.rank + 1), group_name="g")
            return out

    workers = [Worker.remote(i, 4) for i in range(4)]
    outs = ray_tpu.get([w.step.remote() for w in workers])
    for out in outs:
        np.testing.assert_array_equal(out, np.ones(4) * 10)


def test_collective_suite(ray_start):
    @ray_tpu.remote
    class Worker:
        def __init__(self, rank, world):
            col.init_collective_group(world, rank, group_name="g")
            self.rank = rank

        def run(self):
            results = {}
            results["bcast"] = col.broadcast(
                np.full(2, self.rank), src_rank=2, group_name="g"
            )
            results["gather"] = col.allgather(np.asarray([self.rank]), group_name="g")
            results["rs"] = col.reducescatter(np.arange(8.0), group_name="g")
            results["mean"] = col.allreduce(
                np.asarray([float(self.rank)]), group_name="g", op=col.ReduceOp.MEAN
            )
            col.barrier(group_name="g")
            return results

    workers = [Worker.remote(i, 4) for i in range(4)]
    outs = ray_tpu.get([w.run.remote() for w in workers])
    for rank, res in enumerate(outs):
        np.testing.assert_array_equal(res["bcast"], np.full(2, 2))
        np.testing.assert_array_equal(np.concatenate(res["gather"]), np.arange(4))
        np.testing.assert_array_equal(res["rs"], np.arange(8.0)[rank * 2 : rank * 2 + 2] * 4)
        np.testing.assert_allclose(res["mean"], [1.5])


def test_send_recv(ray_start):
    @ray_tpu.remote
    class Peer:
        def __init__(self, rank):
            col.init_collective_group(2, rank, group_name="g")
            self.rank = rank

        def exchange(self):
            if self.rank == 0:
                col.send(np.asarray([42]), dst_rank=1, group_name="g")
                return None
            return col.recv(src_rank=0, group_name="g")

    a, b = Peer.remote(0), Peer.remote(1)
    _, got = ray_tpu.get([a.exchange.remote(), b.exchange.remote()])
    np.testing.assert_array_equal(got, [42])


def test_slice_run_gang(ray_start):
    # simulate a 2-host v5p-16 slice on the local node by advertising the
    # slice resources (the multi-node path does this via node registration)
    runtime = rt.get_runtime()
    from ray_tpu.core.resources import ResourceSet

    runtime.node_resources.add_capacity(
        ResourceSet({"TPU": 8.0, "TPU-v5p-16": 2.0})
    )

    def spmd_fn(rank, world_size):
        col.init_collective_group(world_size, rank, group_name="slice")
        total = col.allreduce(np.asarray([rank + 1.0]), group_name="slice")
        return rank, world_size, float(total[0])

    refs = slice_run(spmd_fn, "v5p-16")
    out = ray_tpu.get(refs, timeout=30)
    assert out == [(0, 2, 3.0), (1, 2, 3.0)]
    col.destroy_collective_group("slice")


def test_create_collective_group_declarative(ray_start):
    import numpy as np

    @ray_tpu.remote
    class Member:
        def reduce(self, v):
            return col.allreduce(np.asarray([v], dtype=np.float64), group_name="decl")

    members = [Member.remote() for _ in range(3)]
    col.create_collective_group(members, 3, [0, 1, 2], group_name="decl")
    outs = ray_tpu.get([m.reduce.remote(float(i)) for i, m in enumerate(members)])
    for out in outs:
        np.testing.assert_array_equal(out, [3.0])
    col.destroy_collective_group("decl")


def test_destroy_then_recreate_group(ray_start):
    import numpy as np

    @ray_tpu.remote
    class M:
        def __init__(self, rank, world, gname):
            col.init_collective_group(world, rank, group_name=gname)

        def red(self, gname):
            return col.allreduce(np.asarray([1.0]), group_name=gname)

    ms = [M.remote(i, 2, "cyc") for i in range(2)]
    ray_tpu.get([m.red.remote("cyc") for m in ms])
    col.destroy_collective_group("cyc")
    # recreate with different membership; stale thread-locals must not leak
    ms2 = [M.remote(i, 3, "cyc") for i in range(3)]
    outs = ray_tpu.get([m.red.remote("cyc") for m in ms2], timeout=30)
    for out in outs:
        np.testing.assert_array_equal(out, [3.0])
    col.destroy_collective_group("cyc")
