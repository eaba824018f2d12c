"""dispatches_per_fit: block ops handed to the backend per fit (the growth
of ``backend_dispatches`` over the window, per fit)."""


def read(run):
    return run.counter_per_job("backend_dispatches")
