"""Milliseconds per step in the flash-attention kernels alone: the Mosaic
calls the program named ``hvd_flash_*`` (device trace, worst device), in a
step that also holds another kernel family, where ``flash_ms`` (every
Mosaic call) holds both. Nothing to read in a program that names no such
kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_flash_")
