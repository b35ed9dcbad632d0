"""Model programs: backend compiles JAX made between window start and end; should be 0."""


def read(run):
    return run["compiles_in_window"]
