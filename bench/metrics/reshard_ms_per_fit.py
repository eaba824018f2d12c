"""reshard_ms_per_fit: host milliseconds per fit inside ``reshard`` calls
(the ``nums:reshard`` spans: building and scheduling each move graph): the
growth of the library's ``reshard_s`` over the window, per fit.  ``None``
where the library has no such counter."""


def read(run):
    v = run.counter_per_job("reshard_s")
    return None if v is None else 1e3 * v
