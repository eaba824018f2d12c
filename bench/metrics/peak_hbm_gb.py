"""peak_hbm_gb: the highest ``peak_bytes_in_use`` over the cell's chips, read
after the window and before any reference runs, in units of 1e9 bytes."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
