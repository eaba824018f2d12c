"""cross_chip_mb_per_fit: megabytes (1e6 bytes) of operands moved from one
chip to another per fit (the ``nums:move`` spans): the growth of the
library's ``backend_device_move_bytes`` over the window, per fit."""


def read(run):
    v = run.counter_per_job("backend_device_move_bytes")
    return None if v is None else v / 1e6
