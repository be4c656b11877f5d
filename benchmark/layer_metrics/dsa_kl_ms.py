"""Milliseconds per step in the index-loss kernels (``pallas_call`` names
that start with ``hvd_dsa_kl``: the heads' mean attention recomputed over
the kept set, the index loss and the indexer's three gradients): device
trace, worst device. Nothing to read without a trace or in a program that
names no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_dsa_kl")
