"""Median milliseconds of the host inside ``step(state, batch)`` until it
returns (host clock, untraced window)."""

from benchmark.lib.stats import percentile


def read(run):
    return percentile([x * 1e3 for x in run["window"]["dispatch_s"]], 50)
