"""dgemm_roofline: percent of the chip's busy time in the traced window that
the products' least device time fills: 2 n^3 operations at the bf16 peak
per product, which bounds it over the bytes of A, B and C at 819 GB/s."""


def read(run):
    return run.roofline_share()
