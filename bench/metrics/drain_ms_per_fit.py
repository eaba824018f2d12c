"""drain_ms_per_fit: host milliseconds per fit inside the outermost drain of
the pipelined executor's queues (the ``nums:drain`` span, which holds every
``nums:dispatch``): the growth of the library's ``drain_s`` over the window,
per fit."""


def read(run):
    v = run.counter_per_job("drain_s")
    return None if v is None else 1e3 * v
