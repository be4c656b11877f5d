"""A traced run's device time by what the program named: for the per-layer
readers (``layer_metrics/<name>.py``).

``run["trace"]`` keeps, per device, the seconds of every operation inside
the traced window under its trace name (``lib/trace.DeviceSummary.
op_seconds``). ``run["built"]`` carries what the compiled step says about
those names: ``labels`` (instruction -> ``op_name``, the path of
``jax.named_scope`` segments and primitives it came from) and
``pallas_call_names`` (the Mosaic custom calls). So a reader can ask for

* :func:`scope_ms`: the operations whose ``op_name`` path holds a scope
  segment, such as a ``jax.named_scope("router")`` a model opens. A fused
  instruction carries ONE ``op_name``, that of the operation the compiler
  built the fusion around (``lib/scopes.py`` says how far to trust that);
* :func:`kernel_ms`: the kernels whose ``pallas_call(name=)`` starts with a
  prefix,

both in milliseconds per step on the worst device, None where nothing
matched (the metric is then left out of the line, never reported as 0):

    from benchmark.lib.by_name import kernel_ms
    def read(run):
        return kernel_ms(run, "hvd_flash_fwd")
"""

from __future__ import annotations

from .scopes import kernel_of


def _worst_ms_per_step(run, wanted) -> float | None:
    """Per device, the seconds of the operations ``wanted(name)`` accepts;
    the largest, in ms per step. None without a trace or a match."""
    trace = run["trace"]
    if trace is None:
        return None
    matched = [
        [t for name, t in d.op_seconds.items() if wanted(name)]
        for d in trace.devices
    ]
    if not any(matched):
        return None
    return max(sum(ts) for ts in matched) / trace.steps * 1e3


def scope_ms(run, segment: str) -> float | None:
    """Operations whose ``op_name`` holds ``segment`` as one whole part of
    its path (``a/segment/b``, not ``a/my_segment_x/b``)."""
    labels = run["built"]["labels"]
    return _worst_ms_per_step(
        run, lambda name: segment in labels.get(name, "").split("/")
    )


def kernel_ms(run, prefix: str) -> float | None:
    """Mosaic kernels whose ``pallas_call`` name starts with ``prefix``."""
    labels = run["built"]["labels"]
    kernels = frozenset(run["built"]["pallas_call_names"])
    return _worst_ms_per_step(
        run, lambda name: name in kernels
        and kernel_of(labels.get(name, ""), name).startswith(prefix),
    )
