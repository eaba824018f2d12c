"""solve_s: wall seconds per iterative solve run to its configuration's
tolerance: the measured window over the solves completed in it."""


def read(run):
    return run.window_s / run.jobs
