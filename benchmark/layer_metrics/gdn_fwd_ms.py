"""Milliseconds per step in the Gated DeltaNet forward kernels: the Mosaic
calls the program named ``hvd_gdn_fwd*`` (device trace, worst device).
With ``gdn_bwd_ms`` it sums to ``gdn_ms``. Nothing to read in a program
that names no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_gdn_fwd")
