"""Percent of the traced window in which no operation ran on the device
(device trace, worst device)."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    return 100.0 * max(d.idle_share for d in t.devices)
