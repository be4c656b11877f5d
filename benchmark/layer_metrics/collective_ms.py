"""Milliseconds per step inside collective operations' intervals (device
trace; union per device, worst device). 0 on one chip."""


def read(run):
    t = run["trace"]
    return None if t is None else t.per_step_ms("collective_s")
