"""Runtime telemetry plane: metrics registry, exporters, native counters.

The observability gap the reference fills with its timeline + autotune
logs (horovod/common/timeline.cc, parameter_manager.cc) and the MPI
characterization study (arXiv:1810.11112) fills with external tracing:
nothing in a running job records per-step wall breakdown, collective
bytes/latency, response-cache hit rates or rescale events *as the job
runs*. This package is that metrics plane:

* :class:`~horovod_tpu.obs.registry.MetricsRegistry` — thread-safe
  counters, gauges and ring-buffer histograms (p50/p95/p99), env-gated
  behind ``HVDTPU_METRICS`` so the disabled cost is one cached boolean
  check per instrumentation site.
* :mod:`~horovod_tpu.obs.export` — per-rank JSON-lines + Prometheus
  textfile exporters and a periodic rank-0 summary aggregated across
  processes with one psum-shaped eager allreduce.
* :mod:`~horovod_tpu.obs.native_bridge` — merges the native runtime's
  process-cumulative counters (``hvt_metrics_*`` C ABI, csrc/metrics.h:
  negotiation cycles, fused tensors, response-cache hits/misses,
  shm-vs-TCP bytes) into every export without forcing a native build.
* :mod:`~horovod_tpu.obs.flops` — the analytic flop/peak model behind
  the step instrumentation's MFU gauge.
* :mod:`~horovod_tpu.obs.trace` — the span-level tracing plane +
  crash/hang flight recorder (``HVDTPU_TRACE``): ring-buffered
  Perfetto ``trace_event`` spans across every plane, dumped per rank
  on signals/escalations and merged clock-aligned by
  ``tools/hvdtpu_trace.py``.

Instrumented layers (all no-ops unless ``HVDTPU_METRICS=1``):
``ops/fusion.py`` (bytes per step, bucket count/fill, pack/unpack trace
time), ``ops/eager.py`` (per-collective latency + bytes + stall age),
``parallel/dp.py`` (step-time breakdown, tokens/s, MFU, plus the
``memplan.peak_bytes`` gauge — the static HBM planner's predicted
per-device peak whenever ``step.memplan()``/``step.lint`` runs),
``runner/elastic_driver.py`` (rescale/blacklist events), and the native
background loop via the C ABI. ``tools/hvdtpu_top.py`` tails the JSONL
files live (the ``hbm plan`` column).

Knobs: ``HVDTPU_METRICS`` (enable), ``HVDTPU_METRICS_DIR`` (export
directory, default ``./hvdtpu_metrics``), ``HVDTPU_METRICS_INTERVAL``
(flush period seconds, default 5).
"""

from __future__ import annotations

from .registry import (  # noqa: F401
    MetricsRegistry,
    always,
    enabled,
    enable,
    disable,
    metrics,
    null_registry,
)
from .export import (  # noqa: F401
    MetricsReporter,
    flush,
    reporter,
    snapshot,
)
from . import build  # noqa: F401
from . import flops  # noqa: F401
from . import goodput  # noqa: F401
from . import trace  # noqa: F401

__all__ = [
    "MetricsRegistry",
    "MetricsReporter",
    "always",
    "enabled",
    "enable",
    "disable",
    "metrics",
    "null_registry",
    "reporter",
    "flush",
    "snapshot",
    "build",
    "flops",
    "goodput",
    "trace",
]
