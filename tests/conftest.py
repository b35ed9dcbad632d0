"""Test fixtures.

Tests run on a virtual 8-device CPU mesh (the analog of the reference's
in-process fake clusters, python/ray/cluster_utils.py:135) so SPMD
sharding paths are exercised without TPU hardware.
"""

import os

# Must be set before jax import anywhere in the test process — and must
# OVERRIDE an inherited JAX_PLATFORMS=tpu: cluster tests spawn
# GCS/daemon/worker subprocesses that inherit this environment, and a
# fleet of CPU test workers must never race each other (or a concurrent
# benchmark) for the one real TPU.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Logical CPU floor for the in-process runtime: local actors are threads,
# so the CPU resource is a concurrency budget, not a core reservation. A
# 1-core CI box must still auto-init enough room for a world_size=2 gang
# (tests that care pass num_cpus explicitly; this only lifts the default).
os.environ.setdefault("RAY_TPU_NUM_CPUS", "8")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs
