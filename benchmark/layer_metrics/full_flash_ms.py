"""Milliseconds per step in the flash kernels of the FULL layers of a
model that also has window layers: the Mosaic calls named ``hvd_flash_*``
without the ``_window`` suffix (device trace, worst device). With
``window_flash_ms`` it sums to ``flash_ms``. Nothing to read where the
program names no such kernel."""

from benchmark.lib.by_kind import flash_ms


def read(run):
    return flash_ms(run, windowed=False)
