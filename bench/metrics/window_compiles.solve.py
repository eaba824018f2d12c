"""window_compiles.solve: executables JAX compiled or loaded from its
persistent cache inside the measured window of a solve cell (expected 0)."""


def read(run):
    return run.compiles
