"""Compiled graph tests (reference test strategy: python/ray/dag/tests/).

Covers: linear chains, fan-out/fan-in, input attributes, pipelining,
multi-output, collective nodes, teardown, error propagation.
"""

import time

import pytest

import ray_tpu
from ray_tpu.dag import CompiledDAG, InputNode, MultiOutputNode
from ray_tpu.dag.nodes import allreduce_bind


@pytest.fixture(autouse=True, scope="module")
def rt():
    """A runtime of the file's own, shut down with it. Under `--dist
    loadfile` an earlier file may have left one on this worker with fewer
    CPUs than this file's actors take and with its own actors still
    holding them (ROADMAP D8's flaky list:
    `test_execute_overflow_raises_not_deadlocks`, `test_teardown_frees_actor`)."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=32)
    yield
    ray_tpu.shutdown()


@ray_tpu.remote
class Worker:
    def __init__(self, scale=1):
        self.scale = scale
        self.calls = 0

    def mul(self, x):
        self.calls += 1
        return x * self.scale

    def add(self, x, y):
        return x + y

    def slow(self, x):
        time.sleep(0.05)
        return x + 1

    def boom(self, x):
        raise ValueError("kaboom")

    def num_calls(self):
        return self.calls


def test_linear_chain():
    a = Worker.remote(2)
    b = Worker.remote(10)
    with InputNode() as inp:
        dag = b.mul.bind(a.mul.bind(inp))
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(3).get() == 60
        assert compiled.execute(5).get() == 100
    finally:
        compiled.teardown()


def test_fan_out_fan_in_same_and_cross_actor():
    a = Worker.remote(2)
    b = Worker.remote(3)
    with InputNode() as inp:
        left = a.mul.bind(inp)       # 2x
        right = b.mul.bind(inp)      # 3x
        dag = a.add.bind(left, right)  # cross-actor arg + same-actor arg
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(4).get() == 8 + 12
    finally:
        compiled.teardown()


def test_input_attributes():
    a = Worker.remote(1)
    with InputNode() as inp:
        dag = a.add.bind(inp["x"], inp["y"])
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute({"x": 7, "y": 8}).get() == 15
    finally:
        compiled.teardown()


def test_multi_output():
    a = Worker.remote(2)
    b = Worker.remote(5)
    with InputNode() as inp:
        dag = MultiOutputNode([a.mul.bind(inp), b.mul.bind(inp)])
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(3).get() == [6, 15]
    finally:
        compiled.teardown()


def test_pipelining_multiple_in_flight():
    a = Worker.remote()
    with InputNode() as inp:
        dag = a.slow.bind(inp)
    compiled = dag.experimental_compile()
    try:
        t0 = time.perf_counter()
        refs = [compiled.execute(i) for i in range(4)]
        assert [r.get() for r in refs] == [1, 2, 3, 4]
        # executions streamed through one loop: results ordered, all correct
        assert time.perf_counter() - t0 < 5
    finally:
        compiled.teardown()


def test_collective_allreduce_node():
    workers = [Worker.remote(s) for s in (1, 2, 3)]
    with InputNode() as inp:
        parts = [w.mul.bind(inp) for w in workers]
        reduced = allreduce_bind(parts)  # sum across actors
        # each worker consumes the same reduced value
        outs = [w.mul.bind(r) for w, r in zip(workers, reduced)]
        dag = MultiOutputNode(outs)
    compiled = dag.experimental_compile()
    try:
        # inp=2 -> parts (2, 4, 6), sum=12 -> outs (12, 24, 36)
        assert compiled.execute(2).get() == [12, 24, 36]
    finally:
        compiled.teardown()


def test_error_propagates_and_unblocks():
    a = Worker.remote()
    with InputNode() as inp:
        dag = a.boom.bind(inp)
    compiled = dag.experimental_compile()
    try:
        ref = compiled.execute(1)
        with pytest.raises(Exception):
            ref.get(timeout=10)
    finally:
        compiled.teardown()


def test_midpipeline_failure_unblocks_driver():
    """Poison must propagate through intermediate loops to the driver."""
    a = Worker.remote()
    b = Worker.remote(2)
    c = Worker.remote(3)
    with InputNode() as inp:
        dag = c.mul.bind(b.mul.bind(a.boom.bind(inp)))
    compiled = dag.experimental_compile()
    try:
        ref = compiled.execute(1)
        with pytest.raises(Exception):
            ref.get(timeout=10)
    finally:
        compiled.teardown()


def test_execute_overflow_raises_not_deadlocks():
    a = Worker.remote(2)
    with InputNode() as inp:
        dag = a.slow.bind(inp)
    compiled = dag.experimental_compile(max_in_flight=2)
    try:
        refs = [compiled.execute(i) for i in range(2)]
        with pytest.raises(RuntimeError, match="in.flight"):
            compiled.execute(99)
        [r.get() for r in refs]
        compiled.execute(3).get()  # drained: works again
    finally:
        compiled.teardown()


def test_teardown_frees_actor():
    a = Worker.remote(2)
    with InputNode() as inp:
        dag = a.mul.bind(inp)
    compiled = dag.experimental_compile()
    assert compiled.execute(2).get() == 4
    compiled.teardown()
    # actor usable again after teardown (loop task completed)
    assert ray_tpu.get(a.num_calls.remote(), timeout=10) == 1
    with pytest.raises(RuntimeError):
        compiled.execute(1)
