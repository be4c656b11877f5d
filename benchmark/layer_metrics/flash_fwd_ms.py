"""Milliseconds per step in the forward kernel of flash attention: the
Mosaic calls the program named ``hvd_flash_fwd`` (device trace, worst
device). With its two siblings it sums to ``flash_ms``. Nothing to read
where the cell's attention bypasses the kernels."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_flash_fwd")
