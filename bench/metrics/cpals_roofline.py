"""cpals_roofline: percent of the chip's busy time in the traced window that
the CP-ALS fits' least device time fills.  Per fit the least time is the
larger of 3 S 2 n^3 F operations at the bf16 peak (S sweeps of three
MTTKRPs) and 4 n^3 (3 S + 4) bytes at 819 GB/s (one read of the float32
tensor per MTTKRP, a read and a write of it per layout change): the bytes
bound it."""


def read(run):
    return run.roofline_share()
