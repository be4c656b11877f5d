"""Milliseconds per step in the Kimi Delta Attention backward kernels: the
Mosaic calls the program named ``hvd_kda_bwd*`` (device trace, worst
device). With ``kda_fwd_ms`` it sums to ``kda_ms``. Nothing to read in a
program that names no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_kda_bwd")
