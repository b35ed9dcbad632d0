"""Process-environment helpers shared by every process-spawning site."""

from __future__ import annotations

import os


def inject_framework_pythonpath(env: dict) -> dict:
    """Prepend the framework root to env's PYTHONPATH (in place).

    Every spawned process (workers, job drivers, dashboards) must import
    ray_tpu regardless of its cwd — a runtime_env working_dir or an
    arbitrary entrypoint directory drops the implicit cwd-based import.
    """
    import ray_tpu

    fw_root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    env["PYTHONPATH"] = (
        fw_root + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else fw_root
    )
    return env


# What JAX_PLATFORMS said where the cluster was launched, carried past the
# CPU pin below to the one kind of child that may open an accelerator.
LAUNCH_PLATFORMS_VAR = "RAY_TPU_LAUNCH_JAX_PLATFORMS"


def pin_control_plane_to_cpu(env: dict) -> dict:
    """Pin a GCS / dashboard / node-daemon child to the CPU backend (in
    place). A chip belongs to one process at a time, so the control
    plane and every worker without a TPU lease must never open it; the
    launching environment's own JAX_PLATFORMS is remembered for the
    workers that do hold one (`unpin_for_accelerator_worker`)."""
    env.setdefault(LAUNCH_PLATFORMS_VAR, env.get("JAX_PLATFORMS", ""))
    env["JAX_PLATFORMS"] = "cpu"
    return env


def unpin_for_accelerator_worker(env: dict) -> dict:
    """Undo the pin (in place) for a worker started for a lease that
    holds TPU chips: it gets the launch environment's platform choice."""
    want = env.get(LAUNCH_PLATFORMS_VAR)
    if want:
        env["JAX_PLATFORMS"] = want
    elif want is not None:
        env.pop("JAX_PLATFORMS", None)  # launcher had none set: JAX decides
    return env
