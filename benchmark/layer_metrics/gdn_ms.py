"""Milliseconds per step in the Gated DeltaNet kernels: the Mosaic calls
the program named ``hvd_gdn_*`` (device trace, worst device), forward and
backward; ``gdn_fwd_ms`` + ``gdn_bwd_ms``. Should the retreat to the
channel-wise kernels on a broadcast gate ever be taken, the names to read
are that form's (``hvd_kda_*``). Nothing to read in a program that names
no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_gdn_")
