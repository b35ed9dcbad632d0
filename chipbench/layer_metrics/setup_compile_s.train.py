"""Model programs: seconds this process spent compiling programs or loading them from the
persistent cache before the window opened, from the program's own compile log
(ray_tpu.obs.compile_log(): one entry per jax.monitoring backend-compile event, started by
configure_compile_cache()). The part of setup_s that a warm cache and fewer programs shorten.
None where the program keeps no such log."""

from chipbench import readers_setup


def read(run):
    before = readers_setup.compiles_before_window(run)
    return None if before is None else sum(seconds for _, _, seconds, _ in before)
