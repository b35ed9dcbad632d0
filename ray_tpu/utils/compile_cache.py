"""Where JAX's persistent compilation cache lives.

A cold chip run is mostly compiling, and the cache key includes the
cache directory's path, so a directory that moves never hits. Every
entry point that opens a backend (chip_smoke.py, bench.py, the device
benchmarks, cluster workers) calls `configure_compile_cache()` first,
and nothing else in the tree sets a cache directory:

 * `JAX_COMPILATION_CACHE_DIR` set from outside — JAX reads it itself;
   this module sets nothing (the chip machine places its cache there
   and carries it from one call to the next);
 * otherwise `<checkout>/.jax_cache`, derived from this package's own
   location — never from tempfile, a pid or a time — so every process
   of a run, and the next run of the same checkout, share one cache;
 * except in a process whose `JAX_PLATFORMS` names no TPU (tier-1, the
   CPU smokes, control-plane children): the cache is for the chip's
   minute-long compiles, and XLA:CPU reloads its own entries with
   machine-feature complaints on stderr.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache")


def asked_for_another_platform() -> bool:
    """JAX_PLATFORMS is set and names no TPU: the CPU on purpose."""
    want = os.environ.get("JAX_PLATFORMS", "")
    return bool(want) and "tpu" not in want


def configure_compile_cache() -> Optional[str]:
    """Place the compile cache; returns the directory in use (None when
    this process asked for a platform other than the TPU). Call before
    the first backend touch of the process."""
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    if asked_for_another_platform():
        return None
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def count_cache_entries(path: Optional[str]) -> int:
    """Compiled programs stored under `path` (0 when there is none)."""
    if path is None:
        return 0
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
