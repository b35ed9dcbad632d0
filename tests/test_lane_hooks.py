"""What tests/conftest.py does to every case of the lane: an alarm that fails
a case that waits forever, alone; and one compile cache a run for the
lane's CPU programs, set in this process and in no child's environment."""

import os
import signal
import tempfile
import time
import types

import jax
import pytest

CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "conftest.py")


@pytest.fixture
def lane(request):
    """tests/conftest.py as pytest loaded it (a module named `conftest`
    may be another directory's)."""
    return next(p for p in request.config.pluginmanager.get_plugins()
                if getattr(p, "__file__", None) == CONFTEST)


def test_a_case_that_waits_forever_fails_alone_with_the_hooks_message(lane, request, monkeypatch):
    monkeypatch.setattr(lane, "CASE_LIMIT_S", 1)
    around = lane.pytest_runtest_call(request.node)
    next(around)                       # the alarm is set; the case runs here
    began = time.monotonic()
    with pytest.raises(TimeoutError, match=r"still running after 1 s .*CASE_LIMIT_S"):
        time.sleep(30)
    assert time.monotonic() - began < 10
    with pytest.raises(StopIteration):
        next(around)                   # and cleared after the case
    assert signal.getitimer(signal.ITIMER_REAL)[0] == 0


@pytest.mark.parametrize("marked,armed", [(None, True), ("slow", False)], ids=["lane", "slow"])
def test_the_alarm_is_the_lanes_and_a_slow_case_has_none(lane, marked, armed):
    """A full-width compile is minutes inside one call; its case is marked
    slow and runs outside the lane's clock."""
    signal.setitimer(signal.ITIMER_REAL, 0)    # this case's own alarm, out of the way
    item = types.SimpleNamespace(nodeid="a case", get_closest_marker=lambda name: name == marked)
    around = lane.pytest_runtest_call(item)
    next(around)
    left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert (0 < left <= lane.CASE_LIMIT_S) if armed else left == 0
    with pytest.raises(StopIteration):
        next(around)
    assert signal.getitimer(signal.ITIMER_REAL)[0] == 0


def test_one_compile_cache_a_run_under_the_temporary_directory_and_in_no_childs_environment(
        request):
    path = jax.config.jax_compilation_cache_dir
    assert os.path.dirname(path) == tempfile.gettempdir() and os.path.isdir(path)
    assert os.path.basename(path).startswith("ray_tpu_lane_xla_")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    # a worker of pytest-xdist has the directory its controller made, not one of its own
    workerinput = getattr(request.config, "workerinput", None)
    if workerinput is not None:
        assert workerinput["lane_compile_cache"] == path
    # the cluster tests' subprocesses and chipbench/tools/aa.py's children inherit none of it
    assert not [k for k in os.environ if "COMPILATION_CACHE" in k or "PERSISTENT_CACHE" in k]
