"""Milliseconds per step in the Kimi Delta Attention kernels: the Mosaic
calls the program named ``hvd_kda_*`` (device trace, worst device; forward
and backward of every KDA layer and a forward that rematerialisation runs
again). ``flash_ms`` holds them too (it reads every Mosaic call); the
latent layer's three flash kernels are ``flash_ms - kda_ms``. Nothing to
read in a program that names no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_kda_")
