"""cross_chip_moves_per_fit: operands moved from one chip to another per
fit (the growth of ``backend_device_moves`` over the window, per fit)."""


def read(run):
    return run.counter_per_job("backend_device_moves")
