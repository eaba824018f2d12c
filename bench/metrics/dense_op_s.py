"""dense_op_s: wall seconds per dense linear-algebra operation, every output
block ready: the measured window over the operations completed in it."""


def read(run):
    return run.window_s / run.jobs
