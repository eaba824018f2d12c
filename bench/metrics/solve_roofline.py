"""solve_roofline: percent of the chips' busy time in the traced window that
the solves' least device time fills.  Per Newton iteration the least time is
the larger of one read of X and y at 819 GB/s and 2 n d^2 + 5 n d operations
at the bf16 peak: the read bounds it."""


def read(run):
    return run.roofline_share()
