"""Milliseconds per step in the Gated DeltaNet backward kernels: the
Mosaic calls the program named ``hvd_gdn_bwd*`` (device trace, worst
device). With ``gdn_fwd_ms`` it sums to ``gdn_ms``. Nothing to read in a
program that names no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_gdn_bwd")
