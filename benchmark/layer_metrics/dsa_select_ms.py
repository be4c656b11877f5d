"""Milliseconds per step in the select kernels (``pallas_call`` names that
start with ``hvd_dsa_select``: the indexer's scores of every causal pair,
each row's exact threshold, the mask and the kept set's log-sum-exp):
device trace, worst device. Nothing to read without a trace or in a program
that names no such kernel."""

from benchmark.lib.by_name import kernel_ms


def read(run):
    return kernel_ms(run, "hvd_dsa_select")
