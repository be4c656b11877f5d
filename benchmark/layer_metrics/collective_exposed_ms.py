"""The part of ``collective_ms`` in which no other operation runs on that
device (device trace, worst device)."""


def read(run):
    t = run["trace"]
    return None if t is None else t.per_step_ms("collective_exposed_s")
