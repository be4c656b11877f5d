"""Milliseconds per step in every device operation that is neither a
Mosaic kernel nor a collective: the model's matmuls and elementwise work,
the loss and the optimizer update, which the trace cannot tell apart until
the program names its scopes (device trace, worst device)."""


def read(run):
    t = run["trace"]
    return None if t is None else t.per_step_ms("other_s")
