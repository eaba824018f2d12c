"""window_compiles.dense: executables JAX compiled or loaded from its
persistent cache inside the measured window of a dense-operation cell
(expected 0)."""


def read(run):
    return run.compiles
