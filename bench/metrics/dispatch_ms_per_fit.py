"""dispatch_ms_per_fit: host milliseconds per fit issuing compiled block ops
whose executable was cached (the ``nums:dispatch`` spans, inside
``nums:drain``): the growth of the library's ``backend_dispatch_s`` over the
window, per fit."""


def read(run):
    v = run.counter_per_job("backend_dispatch_s")
    return None if v is None else 1e3 * v
