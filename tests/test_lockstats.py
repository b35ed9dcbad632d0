"""cluster/lockstats.TimedRLock: wait/hold histograms of the GCS locks
(≈0 wait uncontended, visible wait under seeded contention)."""

from __future__ import annotations

import threading
import time

import pytest

pytestmark = pytest.mark.perfwatch


def _wait_stats(domain):
    from ray_tpu.cluster.lockstats import lock_wait_histogram

    hist = lock_wait_histogram()
    data = hist.hist_data().get((domain,))
    if data is None:
        return 0, 0.0
    _, total_ms, count = data
    return count, total_ms


class TestTimedRLock:
    def test_uncontended_wait_is_near_zero(self):
        from ray_tpu.cluster import lockstats

        domain = f"test_uncontended_{time.monotonic_ns()}"
        lk = lockstats.TimedRLock(domain)
        lockstats.enable_lock_timing(True)
        try:
            for _ in range(200):
                with lk:
                    pass
        finally:
            lockstats.enable_lock_timing(False)
        count, total_ms = _wait_stats(domain)
        assert count == 200
        # free acquires: mean wait well under a millisecond
        assert total_ms / count < 1.0

    def test_seeded_contention_shows_in_wait(self):
        from ray_tpu.cluster import lockstats

        domain = f"test_contended_{time.monotonic_ns()}"
        lk = lockstats.TimedRLock(domain)
        held = threading.Event()
        release = threading.Event()

        def holder():
            with lk:
                held.set()
                release.wait(timeout=10.0)

        lockstats.enable_lock_timing(True)
        try:
            t = threading.Thread(target=holder, daemon=True)
            t.start()
            assert held.wait(timeout=10.0)
            timer = threading.Timer(0.05, release.set)
            timer.start()
            with lk:       # blocks ~50ms on the holder
                pass
            t.join(timeout=10.0)
        finally:
            lockstats.enable_lock_timing(False)
        count, total_ms = _wait_stats(domain)
        assert count >= 2  # holder's free acquire + our blocked one
        assert total_ms >= 20.0, f"expected a visible blocked wait, got {total_ms}ms"

    def test_reentrant_acquire_counts_once(self):
        from ray_tpu.cluster import lockstats

        domain = f"test_reentrant_{time.monotonic_ns()}"
        lk = lockstats.TimedRLock(domain)
        lockstats.enable_lock_timing(True)
        try:
            with lk:
                with lk:   # reentrant hop: no second wait observation
                    pass
        finally:
            lockstats.enable_lock_timing(False)
        count, _ = _wait_stats(domain)
        assert count == 1

    def test_timing_off_is_silent(self):
        from ray_tpu.cluster import lockstats

        domain = f"test_off_{time.monotonic_ns()}"
        lk = lockstats.TimedRLock(domain)
        assert not lockstats.lock_timing_enabled()
        with lk:
            pass
        count, _ = _wait_stats(domain)
        assert count == 0

    def test_condition_wait_restores_depth_and_times(self):
        from ray_tpu.cluster import lockstats

        domain = f"test_cond_{time.monotonic_ns()}"
        lk = lockstats.TimedRLock(domain)
        cond = threading.Condition(lk)
        lockstats.enable_lock_timing(True)
        try:
            def notifier():
                with cond:
                    cond.notify_all()

            with cond:
                threading.Timer(0.02, notifier).start()
                assert cond.wait(timeout=5.0)
                assert lk._is_owned()
        finally:
            lockstats.enable_lock_timing(False)
        count, _ = _wait_stats(domain)
        # outermost acquire + the re-acquire after wait() (+ notifier)
        assert count >= 2
