"""Where JAX's persistent compilation cache lives.

A cold chip run is mostly compiling, and the cache key includes the
cache directory's path, so a directory that moves never hits. Every
entry point that opens a backend (chip_smoke.py, the device
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

The same call starts the process's compile log (`compile_log()`, also
`ray_tpu.obs.compile_log()`): one entry per program JAX compiled or
loaded from the cache, which is where the seconds of a start-up go.
"""

from __future__ import annotations

import os
import sys
import threading
import time
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


# -- the compile log ---------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
COMPILE_LOG_MAX = 8192  # entries kept; a serving engine has a few hundred programs

_LOG: list = []         # (time.time() at the end, program name, seconds, how)
_LOG_LOCK = threading.Lock()
_HIT = threading.local()  # a cache hit is announced before its duration, on the same thread
_STARTED = [False]


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        _HIT.pending = True


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    how = "loaded" if getattr(_HIT, "pending", False) else "compiled"
    _HIT.pending = False
    with _LOG_LOCK:
        if len(_LOG) >= COMPILE_LOG_MAX:
            del _LOG[: COMPILE_LOG_MAX // 2]
        _LOG.append((time.time(), str(kw.get("fun_name", "")), float(duration), how))


def start_compile_log() -> None:
    """Register the two `jax.monitoring` listeners, once a process."""
    import jax

    with _LOG_LOCK:
        if _STARTED[0]:
            return
        _STARTED[0] = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_log(since: float = 0.0) -> list:
    """[(time.time() when it ended, program name, seconds, "compiled" |
    "loaded")] for every program this process compiled or loaded from
    the persistent cache at or after `since`, oldest first. Seconds are
    the whole backend step either way (for a load: the retrieval)."""
    with _LOG_LOCK:
        return [e for e in _LOG if e[0] >= since]


def configure_compile_cache() -> Optional[str]:
    """Place the compile cache and start the compile log; returns the
    directory in use (None when this process asked for a platform other
    than the TPU). Call before the first backend touch of the process."""
    if "jax" in sys.modules or not asked_for_another_platform():
        # not in a control-plane child pinned to the CPU that has never
        # imported jax: the log must not be what makes it pay the import
        start_compile_log()
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
