"""Milliseconds per step inside the Mosaic custom calls of the compiled
step (device trace, worst device). 0 where attention bypasses them."""


def read(run):
    t = run["trace"]
    return None if t is None else t.per_step_ms("kernels_s")
