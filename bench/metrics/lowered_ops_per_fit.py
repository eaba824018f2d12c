"""lowered_ops_per_fit: block ops per fit that ran inside compiled segment
programs rather than as calls of their own (the growth of
``backend_lowered_ops`` over the window, per fit).  ``None`` where the
library has no such counter."""


def read(run):
    return run.counter_per_job("backend_lowered_ops")
