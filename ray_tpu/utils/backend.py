"""Opening the JAX backend from an entry point that measures.

A benchmark that wanted the chip must not end on the CPU with exit 0:
`open_backend()` is the one way the device benchmarks reach a device.
It places the compile cache, opens the backend, and refuses anything
but a TPU unless the caller's environment asked for the CPU by name
(`JAX_PLATFORMS` set and naming no TPU — the smoke shapes tier-1 runs).
"""

from __future__ import annotations

import os

from ray_tpu.utils.compile_cache import (
    asked_for_another_platform,
    configure_compile_cache,
)


class NoAcceleratorError(RuntimeError):
    """The process did not ask for the CPU and JAX found no TPU."""


def open_backend():
    """Returns `jax.devices()[0]`; raises NoAcceleratorError when that is
    not a TPU and `JAX_PLATFORMS` did not explicitly ask for another
    platform."""
    configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not asked_for_another_platform():
        raise NoAcceleratorError(
            f"no TPU: jax.devices()[0] is {dev.platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); set JAX_PLATFORMS=cpu "
            "to run the CPU smoke shape on purpose"
        )
    return dev
