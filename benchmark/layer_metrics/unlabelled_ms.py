"""Milliseconds per step in the operations of ``xla_ops_ms`` for which the
compiled step kept no ``op_name``: the compiler's own instructions, such
as the ``copy-done`` of an asynchronous copy it inserted (device trace,
worst device; ``lib/parts.py``)."""

from benchmark.lib.parts import xla_ops_ms_where


def read(run):
    return xla_ops_ms_where(run, lambda label: not label)
