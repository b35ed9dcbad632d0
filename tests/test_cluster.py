"""Distributed runtime tests: real GCS + node-daemon + worker processes.

Mirrors the reference's multi-node strategy (SURVEY §4.3:
ray.cluster_utils.Cluster starting N raylets as local processes) and its
chaos layer (§4.5 node/worker killers) at small scale.
"""

import os
import sys
import time

import cloudpickle
import numpy as np
import pytest

from ray_tpu.cluster import ClusterTaskError, LocalCluster

# test functions/classes must travel by value: the worker processes have
# no tests/ on their import path
cloudpickle.register_pickle_by_value(sys.modules[__name__])


@pytest.fixture(scope="module")
def cluster():
    c = LocalCluster(node_death_timeout_s=2.0)
    c.start()
    c.add_node({"num_cpus": 2}, node_id="head")
    c.add_node({"num_cpus": 2}, node_id="n1")
    c.add_node({"num_cpus": 2, "magic": 1}, node_id="n2")
    c.wait_for_nodes(3)
    yield c
    c.shutdown()


def test_cluster_task_error_survives_pickling():
    """It travels on as the cause of other errors (a gang member's
    failure reaching the trainer's driver): unpickling must not replace
    it with a TypeError."""
    import pickle

    err = ClusterTaskError("step()", ValueError("boom"), "Traceback ...")
    back = pickle.loads(pickle.dumps(err))
    assert isinstance(back, ClusterTaskError)
    assert str(back) == str(err) and "boom" in str(back)


def _worker_env():
    import os

    keys = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS")
    return {k: os.environ.get(k) for k in keys}, os.getpid()


def _hold_chips(pidfile, hold_s):
    """-> (pid, was the chips' previous holder gone when this one started?)"""
    import os
    import time

    prev = open(pidfile).read() if os.path.exists(pidfile) else ""
    prev_gone = not prev or not os.path.exists(f"/proc/{prev}")
    with open(pidfile, "w") as f:
        f.write(str(os.getpid()))
    time.sleep(hold_s)
    return os.getpid(), prev_gone


def test_tpu_lease_gets_a_worker_of_its_own(tmp_path):
    """A chip belongs to one process: a lease that holds TPU gets its own
    worker, isolated to its chips, with the launch environment's platform
    choice (the CPU here) instead of the daemon's pin; it dies with the
    lease, and a lease waiting for its chips gets them only once that
    process is gone. Workers without a TPU lease stay pooled and pinned
    to the CPU."""
    c = LocalCluster(node_death_timeout_s=5.0)
    try:
        c.start()
        c.add_node({"num_cpus": 2, "TPU": 2}, node_id="chips")
        c.wait_for_nodes(1)
        client = c.client()
        cpu_env, cpu_pid = client.get(client.submit(_worker_env), timeout=60)
        assert cpu_env == {"JAX_PLATFORMS": "cpu", "TPU_VISIBLE_CHIPS": None,
                           "TPU_CHIPS_PER_HOST_BOUNDS": None}
        one_env, one_pid = client.get(
            client.submit(_worker_env, resources={"TPU": 1}), timeout=60)
        assert one_env["TPU_VISIBLE_CHIPS"] == "0"
        assert one_env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
        assert one_env["JAX_PLATFORMS"] == "cpu"  # what this test process runs under
        all_env, all_pid = client.get(
            client.submit(_worker_env, resources={"TPU": 2}), timeout=60)
        assert all_env["TPU_VISIBLE_CHIPS"] is None  # whole node: runtime defaults
        assert len({cpu_pid, one_pid, all_pid}) == 3  # TPU workers are not reused
        # three leases for the whole node, two of them queued behind the
        # first: each starts in a new process after the last one was reaped
        pidfile = str(tmp_path / "holder.pid")
        refs = [client.submit(_hold_chips, (pidfile, 0.5), resources={"TPU": 2})
                for _ in range(3)]
        held = [client.get(r, timeout=120) for r in refs]
        assert len({pid for pid, _ in held}) == 3
        assert all(prev_gone for _, prev_gone in held), held
        with pytest.raises(ClusterTaskError, match="whole chips"):
            client.get(client.submit(_worker_env, resources={"TPU": 0.5}), timeout=60)
        _, again = client.get(client.submit(_worker_env), timeout=60)
        assert again == cpu_pid  # the CPU worker went back to the pool
    finally:
        c.shutdown()


def _whoami():
    import os

    return (os.environ.get("RAY_TPU_NODE_ID"), os.getpid())


def test_tasks_execute_in_worker_processes(cluster):
    client = cluster.client()
    ref = client.submit(_whoami)
    node_id, pid = client.get(ref, timeout=60)
    assert node_id in ("head", "n1", "n2")
    assert pid != os.getpid()  # really another process


def test_tasks_spread_across_nodes(cluster):
    client = cluster.client()
    # 6 concurrent 2-cpu tasks cannot fit one 2-cpu node: they must spill

    def hold(t):
        import os
        import time

        time.sleep(t)
        return os.environ.get("RAY_TPU_NODE_ID")

    # 5.0s holds: under a loaded host the third lease can take seconds to land
    # (queued locally until the 0.5s spillback probe fires), and a task
    # that FINISHES before the next one leases frees its node for reuse —
    # the assertion needs all three genuinely overlapping
    refs = [
        client.submit(hold, (5.0,), resources={"num_cpus": 2}) for _ in range(3)
    ]
    nodes = {client.get(r, timeout=120) for r in refs}
    assert len(nodes) == 3, f"expected all 3 nodes used, got {nodes}"


def test_put_get_roundtrip_and_cross_node_transfer(cluster):
    client = cluster.client()
    arr = np.arange(100_000, dtype=np.float32)

    def produce():
        import numpy as np

        return np.ones(200_000, dtype=np.float64)

    # put/get through the head daemon
    ref = client.put({"a": arr, "n": 7})
    out = client.get(ref)
    np.testing.assert_array_equal(out["a"], arr)
    # result produced on SOME node, pulled through the head daemon
    big = client.get(client.submit(produce), timeout=60)
    assert big.shape == (200_000,) and big[0] == 1.0


def test_task_dependencies_cross_node(cluster):
    client = cluster.client()

    def make():
        return list(range(100))

    def consume(xs, scale):
        return sum(xs) * scale

    ref = client.submit(make)
    # magic resource forces consume onto n2 while make ran anywhere
    out = client.submit(
        consume, (ref, 2), resources={"num_cpus": 1, "magic": 1}
    )
    assert client.get(out, timeout=60) == sum(range(100)) * 2


def test_error_propagation(cluster):
    client = cluster.client()

    def boom():
        raise ValueError("kaboom")

    with pytest.raises(ClusterTaskError, match="kaboom"):
        client.get(client.submit(boom), timeout=60)


def test_custom_resource_routing(cluster):
    client = cluster.client()
    refs = [
        client.submit(_whoami, resources={"num_cpus": 1, "magic": 1})
        for _ in range(2)
    ]
    for r in refs:
        node_id, _ = client.get(r, timeout=60)
        assert node_id == "n2"  # only n2 has `magic`


class Counter:
    def __init__(self, start=0):
        self.n = start

    def incr(self, by=1):
        self.n += by
        return self.n

    def where(self):
        import os

        return os.environ.get("RAY_TPU_NODE_ID")


def test_actor_create_call_named(cluster):
    client = cluster.client()
    h = client.create_actor(Counter, (10,), name="counter0")
    assert client.get(h.incr.remote(), timeout=60) == 11
    assert client.get(h.incr.remote(5), timeout=60) == 16
    # lookup by name, state shared
    h2 = client.get_named_actor("counter0")
    assert client.get(h2.incr.remote(), timeout=60) == 17
    h.kill()


def test_actor_handle_travels_through_task(cluster):
    client = cluster.client()
    h = client.create_actor(Counter, (0,))

    def poke(counter_handle):
        r = counter_handle.incr.remote(100)
        return r.get(timeout=30)

    out = client.get(client.submit(poke, (h,)), timeout=60)
    assert out == 100
    h.kill()


def test_placement_group_strict_spread(cluster):
    client = cluster.client()
    info = client.create_placement_group(
        [{"num_cpus": 1}, {"num_cpus": 1}], strategy="STRICT_SPREAD"
    )
    nodes = [b["node_id"] for b in info["bundles"]]
    assert len(set(nodes)) == 2
    # tasks in the pg land on the reserved nodes
    r0 = client.submit(
        _whoami, resources={"num_cpus": 1}, pg_id=info["pg_id"], bundle_index=0
    )
    r1 = client.submit(
        _whoami, resources={"num_cpus": 1}, pg_id=info["pg_id"], bundle_index=1
    )
    got = {client.get(r0, timeout=60)[0], client.get(r1, timeout=60)[0]}
    assert got == set(nodes)
    client.remove_placement_group(info["pg_id"])


@pytest.mark.parametrize("mode", ["task_retry", "actor_restart"])
def test_node_death_recovery(mode):
    """Kill the only compute node mid-flight; a rescue node joins and the
    work recovers (task re-executed / actor restarted by the GCS)."""
    with LocalCluster(node_death_timeout_s=1.5) as c:
        c.start()
        # head is a driver-only node (no compute): all work lands on victim
        c.add_node({"num_cpus": 0}, node_id="head")
        c.add_node({"num_cpus": 2}, node_id="victim")
        c.wait_for_nodes(2)
        client = c.client()

        if mode == "task_retry":

            def slow():
                import time

                time.sleep(8)
                return "done"

            ref = client.submit(slow, max_retries=3)
            doomed_ref = client.submit(slow, max_retries=0, desc="no-retries")
            time.sleep(2.0)  # both running on victim
            c.kill_node("victim")
            c.add_node({"num_cpus": 2}, node_id="rescue")
            c.wait_node_dead("victim", timeout=15)
            # retryable task re-executes on the rescue node
            assert client.get(ref, timeout=120) == "done"
            # non-retryable task surfaces the loss
            with pytest.raises(ClusterTaskError, match="lost"):
                client.get(doomed_ref, timeout=120)
        else:
            h = client.create_actor(Counter, (0,), max_restarts=2)
            assert client.get(h.incr.remote(), timeout=60) == 1
            c.kill_node("victim")
            c.add_node({"num_cpus": 2}, node_id="rescue")
            c.wait_node_dead("victim", timeout=15)
            # GCS restarts the actor on the rescue node (fresh state)
            deadline = time.monotonic() + 60
            val = None
            while time.monotonic() < deadline:
                try:
                    val = client.get(h.incr.remote(), timeout=20)
                    break
                except ClusterTaskError:
                    time.sleep(0.5)
            assert val == 1  # restarted from scratch
            assert client.get(h.where.remote(), timeout=30) == "rescue"


def test_node_affinity_routing(cluster):
    client = cluster.client()
    # hard affinity: lands exactly on the named node
    for target in ("head", "n1", "n2"):
        ref = client.submit(_whoami, affinity_node_id=target)
        node_id, _ = client.get(ref, timeout=60)
        assert node_id == target
    # hard affinity to a nonexistent node: the task fails, not silently runs
    ref = client.submit(_whoami, affinity_node_id="no-such-node", max_retries=0)
    with pytest.raises(ClusterTaskError, match="not alive"):
        client.get(ref, timeout=60)
    # soft affinity to a dead node: falls back to any node
    ref = client.submit(
        _whoami, affinity_node_id="no-such-node", affinity_soft=True
    )
    node_id, _ = client.get(ref, timeout=60)
    assert node_id in ("head", "n1", "n2")


def test_kill_remote_actor_releases_lease(cluster):
    """Killing an actor on a REMOTE node must release its lease there:
    the node's availability is restored and its dedicated worker reaped
    (regression: release used to always go to the driver's local daemon)."""
    client = cluster.client()
    h = client.create_actor(Counter, (0,), resources={"num_cpus": 1, "magic": 1})
    assert client.get(h.where.remote(), timeout=60) == "n2"  # only n2 has magic
    nodes = {n["node_id"]: tuple(n["addr"]) for n in client.nodes()}
    stats = client.pool.get(nodes["n2"]).call("stats", None)
    assert stats["available"].get("magic", 0) == 0  # lease holds the resource
    h.kill()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        stats = client.pool.get(nodes["n2"]).call("stats", None)
        if stats["available"].get("magic", 0) == 1 and stats["num_leases"] == 0:
            break
        time.sleep(0.2)
    assert stats["available"].get("magic", 0) == 1, stats
    assert stats["num_leases"] == 0, stats


def test_object_store_spills_over_capacity_and_frees_on_ref_drop():
    """Byte-capped LRU memory tier + disk spill (reference: plasma
    eviction_policy.h:105 + local_object_manager.h:41 spilling), and
    driver ref-drop freeing objects cluster-wide."""
    import gc

    with LocalCluster(node_death_timeout_s=2.0) as c:
        c.start()
        c.add_node(
            {"num_cpus": 1}, node_id="s0", object_capacity_bytes=1 << 20
        )
        c.wait_for_nodes(1)
        client = c.client()

        # 12 x 256 KiB = 3 MiB through a 1 MiB memory tier
        blobs = [os.urandom(256 << 10) for _ in range(12)]
        refs = [client.put(b) for b in blobs]
        addr = tuple(client.nodes()[0]["addr"])
        stats = client.pool.get(addr).call("stats", None)["objects"]
        assert stats["bytes"] <= (1 << 20) + (256 << 10), stats  # capped
        assert stats["spilled"] > 0, stats  # over-capacity spilled, not lost
        # every object still readable (spilled ones reload from disk)
        for ref, blob in zip(refs, blobs):
            assert client.get(ref, timeout=30) == blob

        # dropping the last driver handle frees cluster-wide
        freed_id = refs[0].id
        del refs[0]
        gc.collect()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            locs = client.gcs.call("locate_object", {"object_id": freed_id})
            if not locs:
                break
            time.sleep(0.1)
        assert not client.gcs.call("locate_object", {"object_id": freed_id})
        # the survivors are untouched
        assert client.get(refs[0], timeout=30) == blobs[1]


def test_gcs_fault_tolerance(tmp_path_factory):
    """kill -9 the GCS mid-workload; restart it at the same address with
    the snapshot: nodes re-register by heartbeat, the named actor is
    still resolvable, objects are re-locatable, and new tasks run
    (reference: Redis-backed GCS restart, redis_store_client.h:107 +
    gcs_init_data.cc replay)."""
    persist = str(tmp_path_factory.mktemp("gcsft") / "gcs.snap")
    with LocalCluster(node_death_timeout_s=2.0, gcs_persist_path=persist) as c:
        c.start()
        c.add_node({"num_cpus": 2}, node_id="ft0")
        c.wait_for_nodes(1)
        client = c.client()

        h = client.create_actor(Counter, (7,), name="survivor")
        assert client.get(h.incr.remote(), timeout=60) == 8
        ref = client.put({"payload": 123})
        time.sleep(0.8)  # let the debounced snapshot land

        c.kill_gcs()
        time.sleep(0.5)
        c.restart_gcs()

        # nodes re-register on their next heartbeat after the restart
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            try:
                alive = [n for n in client.nodes() if n["alive"]]
                if alive:
                    break
            except Exception:
                pass
            time.sleep(0.2)
        assert [n for n in client.nodes() if n["alive"]], "node did not re-register"

        # named actor survived (state intact: the worker process never died)
        h2 = client.get_named_actor("survivor")
        assert client.get(h2.incr.remote(), timeout=60) == 9
        # object directory rebuilt from node inventory
        assert client.get(ref, timeout=30) == {"payload": 123}
        # and fresh work schedules
        assert client.get(client.submit(_whoami), timeout=60)[0] == "ft0"
        h2.kill()


def test_cluster_task_tracing(cluster):
    """Driver-side spans for cluster tasks: lease + exec slices per task,
    exported Chrome-trace (reference: `ray timeline` via GcsTaskManager
    task events)."""
    client = cluster.client()
    client.get([client.submit(_whoami) for _ in range(5)], timeout=60)
    stats = client.task_stats()
    assert stats["tasks"] >= 5
    assert stats["exec_ms_p50"] > 0
    events = client.timeline()
    assert len(events) >= 10  # lease + exec per task
    assert {e["cat"] for e in events} == {"lease", "exec"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)


@pytest.mark.trace
def test_trace_context_rides_task_envelope(cluster):
    """ray_tpu.obs: a TraceContext active at submit time travels inside
    the task envelope — the worker process executes under (a child of)
    the caller's trace, and the driver-side timeline spans carry the
    trace id so cluster work nests under the originating request."""
    from ray_tpu import obs

    client = cluster.client()

    def traced_work():
        from ray_tpu.obs import context as tc

        cur = tc.current()
        return cur.trace_id if cur else None

    with obs.span("cluster.request_root") as ctx:
        got = client.get(client.submit(traced_work), timeout=60)
    assert got == ctx.trace_id, "worker executed outside the caller's trace"
    # the driver span lands on the submitter thread's finally AFTER the
    # return object is readable: poll briefly
    deadline = time.time() + 5
    events = []
    while time.time() < deadline and not events:
        events = [
            e for e in client.timeline()
            if e.get("args", {}).get("trace_id") == ctx.trace_id
        ]
        if not events:
            time.sleep(0.05)
    assert events, "driver lease/exec spans lost the trace id"


def test_task_returns_ride_shared_memory(cluster):
    """Task results are sealed into the C++ shared-memory store by the
    WORKER and adopted (pinned) by the daemon — the bytes never cross the
    put RPC (reference: plasma client seal + raylet adoption)."""
    client = cluster.client()

    def blob():
        return b"z" * 200_000  # above the 64KB shm threshold

    refs = [client.submit(blob) for _ in range(4)]
    for r in refs:
        assert client.get(r, timeout=60) == b"z" * 200_000
    shm_objects = 0
    for n in client.nodes():
        st = client.pool.get(tuple(n["addr"])).call("stats", None)["objects"]
        held = st.get("shm_objects", 0)
        shm_objects += held
        if held:  # a node holding shm objects must show shm bytes in use
            assert st["shm"]["used"] > 0
    assert shm_objects >= 4, "results did not land in the shm tier"


def test_memory_monitor_kills_runaway_worker_and_task_retries(tmp_path):
    """Reference: raylet worker_killing_policy.cc — a worker blowing the
    RSS cap is killed by the daemon's memory monitor; the task's pusher
    sees the connection drop and RE-LEASES it (max_retries), and the
    retry (which no longer over-allocates: transient pressure) completes.
    """
    marker = str(tmp_path / "attempt.marker")

    def greedy(marker_path):
        import os as _os
        import time as _t

        if not _os.path.exists(marker_path):
            with open(marker_path, "w") as f:
                f.write("1")
            # ~600MB over-allocation, far over the cap; park until killed
            hog = bytearray(600 << 20)
            hog[::4096] = b"x" * len(hog[::4096])  # touch pages
            _t.sleep(60)
            return "survived-over-limit"  # must never happen
        return "completed-on-retry"

    with LocalCluster(node_death_timeout_s=5.0) as cluster:
        cluster.start()
        # cap must clear a worker's BASELINE footprint (~170MB with the
        # jax import) but sit far under the hog's allocation
        cluster.add_node({"num_cpus": 1}, node_id="memnode",
                         worker_rss_limit_mb=400)
        cluster.wait_for_nodes(1)
        client = cluster.client()
        ref = client.submit(
            greedy, (marker,), resources={"num_cpus": 1}, max_retries=3
        )
        out = client.get(ref, timeout=120)
        assert out == "completed-on-retry"
        # the daemon recorded the OOM kill
        stats = client.local_daemon.call("stats", None)
        assert stats["num_oom_kills"] >= 1, stats
