"""What JAX built, for which function, and for how long.

A train step that compiles twice pays minutes for it, and the second
build happens inside a ``step(state, batch)`` call where no outside clock
reaches. JAX reports every trace, lowering and backend compile to
``jax.monitoring`` with the function's name and its start and end; one
listener, installed by ``hvd.init()``, books them:

* counters ``build.traces.<fn>`` / ``build.lowerings.<fn>`` /
  ``build.compiles.<fn>`` and gauges of cumulative seconds
  ``build.trace_s.<fn>`` / ``build.lower_s.<fn>`` /
  ``build.compile_s.<fn>``, in the process registry whether or not
  ``HVDTPU_METRICS`` is set (builds are rare: a reader can only ask after
  the run, and the exporters stay gated on the plane);
* with ``HVDTPU_TRACE`` on, a ring span ``hvd.build`` per build of
  ``RING_MIN_S`` or longer with the phase, the function and the call
  number of the step wrapper it happened inside (``None`` outside any),
  so a flight dump says which step recompiled and for how long. JAX also
  reports the trace of every ``jnp`` function nested in a larger trace,
  thousands for a model; they are counted, but a span each would push
  everything else out of the ring, and a build shorter than a step
  explains no stall.

``<fn>`` is one key for the three events: JAX gives the function's name
when tracing and the module's (``jit(<name>)``) when lowering and
compiling. ``dp.make_train_step`` names its jitted function
``hvd_train_step``.
"""

from __future__ import annotations

import threading

from . import registry as _registry
from . import trace as _trace

# jax.monitoring event -> (counter stem, seconds stem, phase)
_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("traces", "trace_s", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lowerings", "lower_s", "lower"),
    "/jax/core/compile/backend_compile_duration":
        ("compiles", "compile_s", "compile"),
}
# A name is a function's ``__name__``, so a program has a few dozen; the
# cap keeps a pathological one from growing the registry without bound.
MAX_FUNCTIONS = 512
OVERFLOW_KEY = "_other"
RING_MIN_S = 0.05

_installed = False
_install_lock = threading.Lock()
_seen: set = set()
# The call number of the train-step wrapper this thread is inside
# (``in_step.call``; unset or None outside), set by ``dp._finish``.
in_step = threading.local()


def function_key(fun_name) -> str:
    """``hvd_train_step``, ``jit(hvd_train_step)`` and
    ``jit_hvd_train_step`` are one function."""
    name = str(fun_name or "unknown")
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    elif name.startswith("jit_"):
        name = name[4:]
    if name not in _seen:
        if len(_seen) >= MAX_FUNCTIONS:
            return OVERFLOW_KEY
        _seen.add(name)
    return name


def _on_time_span(event, start_s, end_s, fun_name=None, **_):
    stems = _EVENTS.get(event)
    if stems is None:
        return
    count, seconds, phase = stems
    fn = function_key(fun_name)
    reg = _registry.always()
    reg.counter(f"build.{count}.{fn}").inc()
    reg.gauge(f"build.{seconds}.{fn}").add(end_s - start_s)
    if end_s - start_s >= RING_MIN_S:
        _trace.complete(
            "hvd.build", "build", start_s, end_s - start_s,
            args={"phase": phase, "fn": fn,
                  "step_call": getattr(in_step, "call", None)},
        )


def install() -> None:
    """Register the listener, once per process (``hvd.init()`` calls it)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax

        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _installed = True
