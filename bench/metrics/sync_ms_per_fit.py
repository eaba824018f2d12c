"""sync_ms_per_fit: host milliseconds per fit blocked on the device, waiting
for a block or copying it to the host (the ``nums:sync`` spans): the growth of
the library's ``backend_sync_s`` over the window, per fit."""


def read(run):
    v = run.counter_per_job("backend_sync_s")
    return None if v is None else 1e3 * v
