"""A traced step split by what the program says it is doing.

``lib/trace.py`` reduces a trace to busy, kernels, collectives and the
rest. Since the program names its phases (``jax.named_scope``: ``hvd_grad``,
``hvd_reduce``, ``hvd_update``, ``hvd_loss_avg``, in every instruction's
``op_name``), its kernels (``pallas_call(name=)``) and its host spans
(``hvd.*`` annotations on the profiler's clock), the same events split
further:

* every device operation inside the window goes to exactly one of: a
  kernel (a Mosaic custom call, under the kernel's own name), a collective,
  or a phase: ``forward`` / ``backward`` (both ``hvd_grad``; JAX marks the
  backward ``transpose(jvp(...))``), ``reduce``, ``update``, ``loss_avg``,
  ``unscoped`` (an ``op_name`` with no scope in it) or ``unlabelled`` (no
  ``op_name`` at all: the compiler's own instructions, such as the
  ``copy-done`` of an asynchronous copy it inserted);
* a fused instruction carries ONE ``op_name``, that of the operation the
  compiler built the fusion around (a weight's gradient matmul, not the
  AdamW update fused behind it). The whole fusion is counted under that
  label's phase; ``mixed_ms`` says how much of each phase's time sits in
  fusions that also hold another SCOPE's operations, and ``mixed_with_ms``
  with which: how far to trust the cut;
* every idle gap is put down to the innermost host span over it: an
  ``hvd.*`` span of the program, else a span of the benchmark's loop.

Pure but for :func:`read_xplane`: events are ``[plane, line, name,
start_ns, duration_ns]`` as ``lib/trace.py`` has them, ``labels`` maps an
HLO instruction's name to its ``op_name``
(``lib/compile_info.instruction_labels``).
"""

from __future__ import annotations

import re
from typing import Sequence

from .trace import (
    ASYNC_LINE, DEVICE_PLANE, LOOP_SPANS, OP_LINE, clip,
    collective_intervals, instruction_name, is_collective, length, overlap,
    subtract, union, window_from_syncs,
)

PROGRAM_SPAN_PREFIX = "hvd."
PHASES = ("forward", "backward", "reduce", "update", "loss_avg", "unscoped",
          "unlabelled")
_SCOPE_PHASE = (
    ("hvd_reduce", "reduce"), ("hvd_update", "update"),
    ("hvd_loss_avg", "loss_avg"),
)
_ONE_SCOPE = {"forward": "grad", "backward": "grad"}  # the rest: their own


def phase_of(label: str) -> str:
    """The phase an ``op_name`` lies in."""
    if not label:
        return "unlabelled"
    parts = label.split("/")
    for scope, phase in _SCOPE_PHASE:
        if scope in parts:
            return phase
    if "hvd_grad" in parts:
        return "backward" if "transpose(" in label else "forward"
    return "unscoped"


def kernel_of(label: str, fallback: str) -> str:
    """The name a Pallas kernel was given: ``pallas_call(name=)`` opens a
    scope of that name around the call, so it is the label's last part
    (before the primitive's own name, where the compiler kept that)."""
    parts = [p for p in label.split("/") if p and p != "pallas_call"]
    return parts[-1] if parts else fallback


# -- which phases a fused computation holds -------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def fusion_phases(hlo: str) -> dict:
    """``{instruction name: {phase: operations}}`` for every instruction of
    the compiled HLO that calls a fused computation: the phases of the
    labelled operations inside it."""
    inside, calls, current = {}, {}, None
    for line in hlo.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(1)
                inside[current] = {}
            continue
        if line.startswith("}"):
            current = None
            continue
        label = _OP_NAME.search(line)
        phase = phase_of(label.group(1) if label else "")
        if phase not in ("unscoped", "unlabelled"):  # constants mix nothing
            inside[current][phase] = inside[current].get(phase, 0) + 1
        called = _CALLS.search(line)
        name = _INSTRUCTION_LINE.match(line)
        if called and name:
            calls[name.group(1)] = called.group(1)
    return {
        name: inside[computation]
        for name, computation in calls.items() if inside.get(computation)
    }


# ------------------------------------------------------------- reading --

def read_xplane(path: str) -> list:
    """As ``lib/trace.read_xplane``, and the host events the program's own
    spans wrote (``hvd.*``) beside the loop's."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        on_host = plane.name.startswith("/host:")
        for line in plane.lines:
            for ev in line.events:
                if on_device or (on_host and (
                    ev.name in LOOP_SPANS
                    or ev.name.startswith(PROGRAM_SPAN_PREFIX)
                )):
                    events.append([
                        plane.name, line.name, instruction_name(ev.name),
                        float(ev.start_ns), float(ev.duration_ns),
                    ])
    return events


# ------------------------------------------------------------ reduction --

def scopes_inside(phases) -> tuple:
    """The scopes a fused computation's phases lie in, sorted: forward and
    backward are one (``grad``)."""
    return tuple(sorted({_ONE_SCOPE.get(p, p) for p in phases}))


def innermost_span(gap, host_spans: Sequence) -> str:
    """The host span that covers most of ``gap``; of several that cover it
    equally (nested spans), the shortest."""
    best, best_key = "none", (0.0, 0.0)
    for name, s, e in host_spans:
        ov = overlap(gap, (s, e))
        if ov > 0 and (ov, s - e) > best_key:
            best, best_key = name, (ov, s - e)
    return best


def split_device(ops: Sequence, async_ops: Sequence, host_spans: Sequence,
                 lo: float, hi: float, steps: int, labels: dict, *,
                 kernel_names=(), collective_names=(), mixed=None,
                 unit_per_ms: float = 1e6, n_gaps: int = 5) -> dict:
    """One device's window, in milliseconds per step. ``ops``, ``async_ops``
    and ``host_spans`` are ``[(name, start, end)]``."""
    mixed = mixed or {}
    per_step = unit_per_ms * steps
    phases = dict.fromkeys(PHASES, 0.0)
    mixed_ms = dict.fromkeys(PHASES, 0.0)
    mixed_with, kernels, busy = {}, {}, []
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        busy.append((s, e))
        label = labels.get(name, "")
        if name in kernel_names:
            kernel = kernel_of(label, name)
            kernels[kernel] = kernels.get(kernel, 0.0) + (e - s) / per_step
        elif not is_collective(name, collective_names):
            phase = phase_of(label)
            phases[phase] += (e - s) / per_step
            held = scopes_inside(mixed.get(name, ()))
            if len(held) > 1:
                mixed_ms[phase] += (e - s) / per_step
                key = "+".join(held)
                mixed_with[key] = mixed_with.get(key, 0.0) + (e - s) / per_step
    coll = clip(
        collective_intervals(ops, collective_names, async_ops), lo, hi
    )
    busy = union(busy)
    # longest first, each with the span it is put down to
    gaps = sorted(
        ([innermost_span(g, host_spans), g[1] - g[0]]
         for g in subtract([(lo, hi)], busy)),
        key=lambda named: -named[1],
    )
    idle_by_span = {}
    for span, duration in gaps:
        idle_by_span[span] = idle_by_span.get(span, 0.0) + duration / per_step
    return {
        "busy_ms": length(busy) / per_step,
        "window_ms": (hi - lo) / per_step,
        "phases_ms": phases,
        "mixed_ms": mixed_ms,
        "mixed_with_ms": mixed_with,
        "kernels_ms": kernels,
        "collectives_ms": length(coll) / per_step,
        "idle_ms_by_span": idle_by_span,
        "longest_gaps": [
            [span, duration / unit_per_ms] for span, duration in gaps[:n_gaps]
        ],
    }


def split(events: Sequence, labels: dict, *, kernel_names=(),
          collective_names=(), mixed=None) -> dict:
    """``{"steps", "devices": [per-device split], "host_spans_ms"}`` over
    the window the benchmark's reduction uses (end of the first ``sync``
    span to the end of the last)."""
    device_ops, async_ops, host_spans = {}, {}, []
    for plane, line, name, start, dur in events:
        m = DEVICE_PLANE.match(plane)
        if m:
            into = {OP_LINE: device_ops, ASYNC_LINE: async_ops}.get(line)
            if into is not None:
                into.setdefault(int(m.group(1)), []).append(
                    (instruction_name(name), start, start + dur)
                )
        elif name in LOOP_SPANS or name.startswith(PROGRAM_SPAN_PREFIX):
            host_spans.append((name, start, start + dur))
    if not device_ops:
        raise ValueError(f"no {OP_LINE!r} line on any /device:TPU:<n> plane")
    lo, hi, steps = window_from_syncs(host_spans)
    spans_ms = {}
    for name, s, e in host_spans:
        if lo <= e <= hi:
            spans_ms.setdefault(name, []).append((e - s) / 1e6)
    return {
        "steps": steps,
        "devices": [
            {"device": dev, **split_device(
                ops, async_ops.get(dev, ()), host_spans, lo, hi, steps,
                labels, kernel_names=frozenset(kernel_names),
                collective_names=frozenset(collective_names), mixed=mixed,
            )}
            for dev, ops in sorted(device_ops.items())
        ],
        "host_spans_ms": spans_ms,
    }


def table(result: dict, busy_ms_benchmark: float | None = None) -> str:
    """The worst (busiest) device's split as lines of text."""
    d = max(result["devices"], key=lambda d: d["busy_ms"])
    busy = d["busy_ms"]
    rows = [(p, d["phases_ms"][p], d["mixed_ms"][p]) for p in PHASES]
    rows += [(k, ms, None) for k, ms in sorted(d["kernels_ms"].items())]
    rows.append(("collectives", d["collectives_ms"], None))
    rows.append(("sum", sum(ms for _, ms, _ in rows), None))
    head = (f"device {d['device']}: busy {busy:.3f} ms/step of "
            f"{d['window_ms']:.3f}, {result['steps']} steps")
    if busy_ms_benchmark:
        head += f"; lib/trace.py's busy {busy_ms_benchmark:.3f}"
    out = [head, f"  {'phase / kernel':28s} {'ms/step':>9s} {'% busy':>7s}"
                 "  of it in fusions that mix scopes"]
    for name, ms, mix in rows:
        out.append(
            f"  {name:28s} {ms:9.3f} {100 * ms / busy:6.2f}%"
            + (f"  {mix:9.3f}" if mix is not None else "")
        )
    out.append("  fusions that mix scopes (ms/step): " + (", ".join(
        f"{k} {v:.3f}" for k, v in sorted(d["mixed_with_ms"].items())
    ) or "none"))
    out.append("  idle by host span (ms/step): " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(
            d["idle_ms_by_span"].items(), key=lambda kv: -kv[1]
        )
    ))
    return "\n".join(out)
