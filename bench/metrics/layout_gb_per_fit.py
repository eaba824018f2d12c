"""layout_gb_per_fit: gigabytes (1e9 bytes) per fit written by the block ops
that only change a layout (``slice``, ``concat_blocks``, ``matricize``): the
growth of the library's ``layout_bytes`` over the window, per fit.  ``None``
where the library has no such counter."""


def read(run):
    v = run.counter_per_job("layout_bytes")
    return None if v is None else v / 1e9
