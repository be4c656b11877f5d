"""Milliseconds per step in the dQ backward kernel of flash attention where
the q / k heads and the v / out heads differ in width (latent attention):
the Mosaic calls the program named ``hvd_flash_bwd_dq`` (device trace, worst
device), as ``flash_bwd_dq_ms`` reads them in the cells of one head width.
With its two siblings it sums to ``flash_ms``. Nothing to read where the
program names no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_flash_bwd_dq")
