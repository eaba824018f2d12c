"""device_idle_share.solve: percent of the traced window in which no
operation ran on a chip, as a mean over the cell's chips."""


def read(run):
    return run.idle_share()
