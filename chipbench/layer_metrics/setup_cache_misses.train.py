"""Model programs: programs compiled, not loaded from the persistent cache, before the
window opened (the same log as setup_compile_s.train). 0 on the second run of a checkout;
anything else there is a program whose cache key moves from run to run."""

from chipbench import readers_setup


def read(run):
    before = readers_setup.compiles_before_window(run)
    return None if before is None else sum(1 for e in before if e[3] == "compiled")
