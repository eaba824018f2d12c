"""pygc_ms_per_fit: milliseconds per fit in CPython's cyclic garbage
collector, anywhere in the process (the ``nums:pygc`` spans, which can fall
inside any other span): the growth of the library's ``pygc_s`` over the
window, per fit."""


def read(run):
    v = run.counter_per_job("pygc_s")
    return None if v is None else 1e3 * v
