"""Median milliseconds the loop waits in ``next(batches)``: the program's
prefetch slicing, staging and sharding the next host batch (host clock,
untraced window)."""

from benchmark.lib.stats import percentile


def read(run):
    return percentile([x * 1e3 for x in run["window"]["input_wait_s"]], 50)
