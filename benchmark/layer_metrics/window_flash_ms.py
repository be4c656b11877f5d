"""Milliseconds per step in the flash kernels of the WINDOW layers: the
Mosaic calls the program named ``hvd_flash_*_window`` (device trace, worst
device; forward, dK/dV, dQ and a forward that rematerialisation runs
again). With ``full_flash_ms`` it sums to ``flash_ms``. Nothing to read
where the program names no such kernel."""

from benchmark.lib.by_kind import flash_ms


def read(run):
    return flash_ms(run, windowed=True)
