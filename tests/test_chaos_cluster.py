"""Cluster-mode chaos: deterministic node kills, heartbeat partitions,
and graceful drain against a REAL GCS + node-daemon + worker-process
cluster (the reference's chaos suite shape, python/ray/tests/chaos
tests, at small scale with a seeded schedule instead of ad-hoc
killers)."""

import os
import sys
import tempfile
import time

import cloudpickle
import pytest

from ray_tpu import chaos
from ray_tpu.chaos.runner import ChaosRunner
from ray_tpu.cluster import ClusterTaskError, LocalCluster

pytestmark = pytest.mark.chaos

# test functions/classes travel by value: worker processes have no tests/
cloudpickle.register_pickle_by_value(sys.modules[__name__])


@pytest.fixture(autouse=True)
def _chaos_clean():
    yield
    chaos.uninstall()


class Counter:
    def __init__(self, start):
        self.v = start

    def incr(self):
        self.v += 1
        return self.v

    def where(self):
        import os

        return os.environ.get("RAY_TPU_NODE_ID")


def _tracked(path, hold_s):
    import os
    import time

    with open(path, "a") as f:
        f.write(f"{os.environ.get('RAY_TPU_NODE_ID')}:{os.getpid()}\n")
    time.sleep(hold_s)
    return "done"


def test_node_kill_task_exactly_once_actor_restart_pg_reschedule():
    """One orchestrated PREEMPT_NODE (SIGKILL of daemon + workers), three
    recovery contracts:

     * a leased task is resubmitted EXACTLY once (the _mark_dead
       regression: the marker file shows one victim line + one rescue
       line, never two resubmits, never a lost task);
     * a max_restarts actor is reconstructed on the surviving node;
     * a placement group's bundle is rescheduled AND re-reserved on the
       new node (the re-reservation used to be missing: leases against a
       re-placed bundle failed forever)."""
    marker = tempfile.mktemp(prefix="chaos_kill_")
    sched = chaos.FaultSchedule(21, [
        chaos.FaultSpec(chaos.PREEMPT_NODE, target="victim", at_s=0.3),
    ])
    try:
        with LocalCluster(node_death_timeout_s=1.5) as c:
            c.start()
            c.add_node({"num_cpus": 0}, node_id="head")  # driver-only
            c.add_node({"num_cpus": 4}, node_id="victim")
            c.wait_for_nodes(2)
            client = c.client()

            h = client.create_actor(Counter, (0,), max_restarts=2,
                                    resources={"num_cpus": 1})
            assert client.get(h.incr.remote(), timeout=60) == 1
            pg = client.create_placement_group([{"num_cpus": 1}],
                                               strategy="PACK")
            assert pg["bundles"][0]["node_id"] == "victim"

            ref = client.submit(_tracked, (marker, 2.5), max_retries=3)
            deadline = time.time() + 60
            while time.time() < deadline:
                if os.path.exists(marker) and open(marker).read().count("\n"):
                    break
                time.sleep(0.05)
            assert open(marker).read().startswith("victim:"), \
                "task never started on victim"

            runner = ChaosRunner(sched, cluster=c).start()
            time.sleep(0.6)
            c.add_node({"num_cpus": 4}, node_id="rescue")
            c.wait_node_dead("victim", timeout=30)

            # exactly-once resubmission, completed on the rescue node
            assert client.get(ref, timeout=120) == "done"
            lines = open(marker).read().splitlines()
            assert len(lines) == 2, lines
            assert lines[0].startswith("victim:")
            assert lines[1].startswith("rescue:")

            # actor reconstruction (fresh state) on the rescue node
            deadline = time.time() + 60
            val = None
            while time.time() < deadline:
                try:
                    val = client.get(h.incr.remote(), timeout=20)
                    break
                except ClusterTaskError:
                    time.sleep(0.5)
            assert val == 1
            assert client.get(h.where.remote(), timeout=30) == "rescue"

            # pg bundle rescheduled + re-reserved: a lease works again
            deadline = time.time() + 30
            info = None
            while time.time() < deadline:
                info = client.gcs.call("get_pg", {"pg_id": pg["pg_id"]})
                if (info["state"] == "CREATED"
                        and info["bundles"][0]["node_id"] == "rescue"):
                    break
                time.sleep(0.2)
            assert info and info["bundles"][0]["node_id"] == "rescue", info
            r = client.submit(lambda: 42, resources={"num_cpus": 1},
                              pg_id=pg["pg_id"], bundle_index=0)
            assert client.get(r, timeout=60) == 42
            runner.stop()
            assert [f.kind for f in runner.executed] == [chaos.PREEMPT_NODE]
    finally:
        try:
            os.unlink(marker)
        except OSError:
            pass


@pytest.mark.slow
def test_heartbeat_partition_late_reply_no_double_execution():
    """The _mark_dead regression the other way around: a TRANSIENT
    heartbeat partition (chaos STALL_HEARTBEAT propagated to the daemon
    via env) gets the node declared dead while its leased task keeps
    running. The late completion must win — the node re-registers with
    its object inventory, the driver fetches the result, and the marker
    shows EXACTLY ONE execution (no lineage resubmission of work that
    never failed)."""
    marker = tempfile.mktemp(prefix="chaos_partition_")
    sched = chaos.FaultSchedule(13, [
        # stall 6 consecutive beats (~3s) after the first 4: long enough
        # for the 2s death verdict, short enough that the node recovers
        chaos.FaultSpec(chaos.STALL_HEARTBEAT, site="node.heartbeat",
                        match={"node_id": "victim"}, start_after=4,
                        max_fires=6),
    ])
    chaos.install(sched, propagate_env=True)  # BEFORE add_node (env copy)
    try:
        with LocalCluster(node_death_timeout_s=2.0) as c:
            c.start()
            c.add_node({"num_cpus": 0}, node_id="head")
            c.add_node({"num_cpus": 2}, node_id="victim")
            c.wait_for_nodes(2)
            client = c.client()
            ref = client.submit(_tracked, (marker, 7.0),
                                affinity_node_id="victim", max_retries=3)
            time.sleep(1.0)
            c.wait_node_dead("victim", timeout=30)  # partition verdict
            assert client.get(ref, timeout=120) == "done"
            lines = open(marker).read().splitlines()
            assert len(lines) == 1 and lines[0].startswith("victim:"), lines
            # the partitioned node healed: re-registered and alive again
            alive = {n["node_id"]: n["alive"] for n in client.nodes()}
            assert alive["victim"] is True
    finally:
        chaos.uninstall()
        try:
            os.unlink(marker)
        except OSError:
            pass


def test_node_drain_stops_admission_and_deregisters():
    """Graceful drain: a drained node grants no new leases (work lands on
    the survivor), finishes in-flight work, and deregisters from the
    GCS."""
    with LocalCluster(node_death_timeout_s=5.0) as c:
        c.start()
        c.add_node({"num_cpus": 2}, node_id="head")
        c.add_node({"num_cpus": 2}, node_id="n1")
        c.wait_for_nodes(2)
        client = c.client()
        n1_addr = tuple(c.nodes["n1"].addr)
        # the client's wait is the other waits' of this test: under the lane's load the
        # answer to a drain has taken more than the 10 s this call once gave it
        r = client.pool.get(n1_addr).call(
            "drain", {"timeout_s": 15.0}, timeout=60
        )
        assert r["ok"]
        # drain flag reaches the GCS view, then the node deregisters
        deadline = time.time() + 30
        while time.time() < deadline:
            n1 = next(n for n in client.nodes() if n["node_id"] == "n1")
            if not n1["alive"] or n1.get("draining"):
                break
            time.sleep(0.1)
        assert (not n1["alive"]) or n1.get("draining"), n1

        def whereami():
            import os

            return os.environ.get("RAY_TPU_NODE_ID")

        # new work admits only on the survivor
        refs = [client.submit(whereami) for _ in range(4)]
        nodes = {client.get(r, timeout=60) for r in refs}
        assert nodes == {"head"}, nodes
        # fully deregistered once the drain completes
        deadline = time.time() + 30
        while time.time() < deadline:
            n1 = next(n for n in client.nodes() if n["node_id"] == "n1")
            if not n1["alive"]:
                break
            time.sleep(0.2)
        assert not n1["alive"], n1


@pytest.mark.slow
def test_chaos_soak_repeated_node_kills():
    """Soak: two kill/rescue rounds with retriable work in flight; every
    task completes despite losing its node mid-run."""
    with LocalCluster(node_death_timeout_s=1.5) as c:
        c.start()
        c.add_node({"num_cpus": 0}, node_id="head")
        c.add_node({"num_cpus": 4}, node_id="gen0")
        c.wait_for_nodes(2)
        client = c.client()

        def hold(i):
            import time

            time.sleep(2.0)
            return i * 10

        for round_i in range(2):
            refs = [client.submit(hold, (i,), max_retries=4)
                    for i in range(3)]
            time.sleep(0.8)  # let leases land on the doomed node
            c.kill_node(f"gen{round_i}")
            c.add_node({"num_cpus": 4}, node_id=f"gen{round_i + 1}")
            assert [client.get(r, timeout=180) for r in refs] == [
                0, 10, 20
            ]
