"""sched_ms_per_fit: host milliseconds of scheduling per fit (fingerprints,
plan replay and cold LSHS placement, less dispatch): the growth of the
library's ``sched_overhead_s`` over the window, per fit."""


def read(run):
    v = run.counter_per_job("sched_overhead_s")
    return None if v is None else 1e3 * v
