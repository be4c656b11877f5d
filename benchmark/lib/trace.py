"""From a profiler trace to numbers, with nothing but JAX.

``jax.profiler.trace`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData.from_file`` reads it: planes, their lines, and
events with ``name``, ``start_ns`` and ``duration_ns``. No TensorFlow, no
TensorBoard.

What the reduction uses:

* one plane per chip, ``/device:TPU:<n>``, and in it the line ``XLA Ops``:
  one event per executed HLO instruction. The trace names an event by the
  instruction's whole text (``%fusion.13 = (f32[...]) fusion(...)``); the
  reduction keeps the instruction's name (``fusion.13``), which is how the
  Mosaic kernels and the collectives are found by the names
  ``lib/compile_info.py`` reads from the compiled HLO. The line ``Async XLA
  Ops`` holds one event per asynchronous operation, from its start to its
  done, under the start's name; only its collectives are read;
* the host plane's events named after the loop's own spans (``input_wait``,
  ``dispatch``, ``sync``), written by ``jax.profiler.TraceAnnotation`` on
  the same clock.

Everything below :func:`read_xplane` works on plain tuples, so the tests
feed it hand-built event lists and the recorded fixtures alike.

Definitions (all per device, inside the window, which runs from the end of
the first ``sync`` span to the end of the last one — whole steady steps):

* busy: the union of the op intervals. Idle share: 1 - busy / window.
* a collective's interval: the op's own interval or, for an asynchronous
  pair ``<op>-start.N`` / ``<op>-done.N``, from the start of the first to the
  end of the second. ``collective``: the union of those intervals.
  ``exposed``: the part of that union in which no other op runs.
* ``kernels``: the union of the Mosaic custom-call intervals.
* ``other``: the union of every op that is neither; with synchronous
  collectives this equals busy - kernels - collective.
* an idle gap is a maximal interval with no op; it is attributed to the
  loop span that overlaps it longest, or to ``none``;
* ``op_seconds``: the summed durations, inside the window, of every op by
  its name, for the readers that ask by scope or by kernel name.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
from typing import Iterable, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
LOOP_SPANS = ("input_wait", "dispatch", "sync")
COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_COLLECTIVE_NAME = re.compile(
    r"^(?:%s)(?:-start|-done)?(?:\.\d+)?$" % "|".join(COLLECTIVE_OPS)
)
_ASYNC = re.compile(r"^(.*)-(start|done)((?:\.\d+)?)$")

_INSTRUCTION = re.compile(r"^%([^\s=]+) = ")

Interval = tuple  # (start, end), any one unit throughout


def instruction_name(event_name: str) -> str:
    """``%fusion.13 = (f32[...]) fusion(...)`` -> ``fusion.13``; a name
    that is not an instruction's text stays as it is."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


# ---------------------------------------------------------------- reading --

def read_xplane(path: str) -> tuple:
    """``(events, inventory)``. ``events``: ``[plane, line, name,
    start_ns, duration_ns]`` for every event of a device plane and every
    loop-span event of the host planes. ``inventory``: events per line of
    every plane, so that a trace laid out otherwise says how."""
    from jax.profiler import ProfileData

    events, inventory = [], {}
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        on_host = plane.name.startswith("/host:")
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                if on_device or (on_host and ev.name in LOOP_SPANS):
                    events.append([
                        plane.name, line.name, instruction_name(ev.name),
                        float(ev.start_ns), float(ev.duration_ns),
                    ])
            inventory.setdefault(plane.name, {})[line.name] = n
    return events, inventory


def save_events(events: Sequence, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({"columns": ["plane", "line", "name", "start_ns",
                               "duration_ns"], "events": list(events)}, f)


def load_events(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)["events"]


# ------------------------------------------------------- interval algebra --

def union(intervals: Iterable[Interval]) -> list:
    """Disjoint sorted intervals covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> list:
    """The part of ``a`` (as a union) that no interval of ``b`` covers."""
    out = []
    cover = union(b)
    for s, e in union(a):
        at = s
        for cs, ce in cover:
            if ce <= at:
                continue
            if cs >= e:
                break
            if cs > at:
                out.append((at, cs))
            at = max(at, ce)
            if at >= e:
                break
        if at < e:
            out.append((at, e))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# ------------------------------------------------------------ classifying --

def is_collective(name: str, collective_names=()) -> bool:
    return name in collective_names or bool(_COLLECTIVE_NAME.match(name))


def collective_intervals(ops: Sequence, collective_names=(),
                         async_ops: Sequence = ()) -> list:
    """``ops`` is ``[(name, start, end)]`` of one device's op line,
    ``async_ops`` the same of its asynchronous-operations line, whose
    collectives each stand for their whole start-to-done interval. Pairs each
    ``X-done`` with the open ``X-start`` of its number (else the oldest open
    one); anything else that is a
    collective stands for itself."""
    out, open_starts = [], {}  # op kind -> [(suffix, start)], oldest first
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        if not is_collective(name, collective_names):
            continue
        m = _ASYNC.match(name)
        if m and m.group(2) == "start":
            open_starts.setdefault(m.group(1), []).append((m.group(3), s))
        elif m and m.group(2) == "done":
            # the start of the same number, else the oldest one still open
            pending = open_starts.get(m.group(1), [])
            at = next((i for i, (suffix, _) in enumerate(pending)
                       if suffix == m.group(3)), 0)
            begun = pending.pop(at)[1] if pending else s
            out.append((begun, e))
        else:
            out.append((s, e))
    out += [(s, e) for name, s, e in async_ops
            if is_collective(name, collective_names)]
    # a start whose done lies beyond the trace: its own interval is lost
    # with the pairing, which under-counts by one op at the trace's edge
    return out


# -------------------------------------------------------------- reduction --

@dataclasses.dataclass
class DeviceSummary:
    device: int
    window_s: float
    busy_s: float
    kernels_s: float
    collective_s: float
    collective_exposed_s: float
    other_s: float
    top_ops: list  # [[name, seconds]], most time first
    idle_gaps: list  # [[loop span or "none", seconds]], longest first
    # seconds inside the window of EVERY operation, by its trace name: what
    # a reader sums by scope or by kernel name (``lib/by_name.py``)
    op_seconds: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    steps: int  # whole steps inside the window
    devices: list  # DeviceSummary, by device number
    loop_spans_ms: dict  # span name -> durations inside the trace, in ms

    def worst(self, field: str) -> float:
        return max(getattr(d, field) for d in self.devices)

    def per_step_ms(self, field: str) -> float:
        """Milliseconds per step of ``field`` on the worst device."""
        return self.worst(field) / self.steps * 1e3

    @property
    def busy_s_mean(self) -> float:
        return sum(d.busy_s for d in self.devices) / len(self.devices)


def window_from_syncs(host_spans: Sequence) -> tuple:
    """``(start, end, steps)``: from the end of the first ``sync`` span to
    the end of the last. Each ``sync`` ends when one step has completed, so
    ``n`` of them bound ``n - 1`` whole steps."""
    ends = sorted(e for name, _, e in host_spans if name == "sync")
    if len(ends) < 2:
        raise ValueError(
            f"the trace holds {len(ends)} 'sync' span(s); a window needs 2"
        )
    return ends[0], ends[-1], len(ends) - 1


def summarize_device(device: int, ops: Sequence, host_spans: Sequence,
                     lo: float, hi: float, *, kernel_names=(),
                     collective_names=(), async_ops: Sequence = (),
                     unit_per_s: float = 1e9, n_top: int = 10,
                     n_gaps: int = 5) -> DeviceSummary:
    """``ops`` and ``host_spans`` are ``[(name, start, end)]`` in one unit
    (``unit_per_s`` of them to a second)."""
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
              if min(e, hi) > max(s, lo)]
    busy = union((s, e) for _, s, e in inside)
    kernels = [(s, e) for n, s, e in inside if n in kernel_names]
    coll = clip(
        collective_intervals(ops, collective_names, async_ops), lo, hi
    )
    other = [
        (s, e) for n, s, e in inside
        if n not in kernel_names and not is_collective(n, collective_names)
    ]
    exposed = subtract(coll, other + kernels)
    totals = {}
    for n, s, e in inside:
        totals[n] = totals.get(n, 0.0) + (e - s)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n_top]
    gaps = sorted(subtract([(lo, hi)], busy), key=lambda g: g[0] - g[1])
    named_gaps = []
    for gap in gaps[:n_gaps]:
        best, best_overlap = "none", 0.0
        for name, s, e in host_spans:
            ov = overlap(gap, (s, e))
            if ov > best_overlap:
                best, best_overlap = name, ov
        named_gaps.append([best, (gap[1] - gap[0]) / unit_per_s])
    return DeviceSummary(
        device=device,
        window_s=(hi - lo) / unit_per_s,
        busy_s=length(busy) / unit_per_s,
        kernels_s=length(kernels) / unit_per_s,
        collective_s=length(coll) / unit_per_s,
        collective_exposed_s=length(exposed) / unit_per_s,
        other_s=length(other) / unit_per_s,
        top_ops=[[n, t / unit_per_s] for n, t in top],
        idle_gaps=named_gaps,
        op_seconds={n: t / unit_per_s for n, t in totals.items()},
    )


def summarize(events: Sequence, *, kernel_names=(),
              collective_names=()) -> TraceSummary:
    """``events`` as :func:`read_xplane` returns them."""
    device_ops, async_ops, host_spans, lines_seen = {}, {}, [], set()
    for plane, line, name, start, dur in events:
        m = DEVICE_PLANE.match(plane)
        if m:
            lines_seen.add(line)
            into = {OP_LINE: device_ops, ASYNC_LINE: async_ops}.get(line)
            if into is not None:
                into.setdefault(int(m.group(1)), []).append(
                    (instruction_name(name), start, start + dur)
                )
        elif name in LOOP_SPANS:
            host_spans.append((name, start, start + dur))
    if not device_ops:
        raise ValueError(
            f"no {OP_LINE!r} line on any /device:TPU:<n> plane; device "
            f"lines seen: {sorted(lines_seen)}"
        )
    lo, hi, steps = window_from_syncs(host_spans)
    devices = [
        summarize_device(
            dev, ops, host_spans, lo, hi, kernel_names=frozenset(kernel_names),
            collective_names=frozenset(collective_names),
            async_ops=async_ops.get(dev, ()),
        )
        for dev, ops in sorted(device_ops.items())
    ]
    spans_ms = {name: [] for name in LOOP_SPANS}
    for name, s, e in host_spans:
        spans_ms[name].append((e - s) / 1e6)
    return TraceSummary(
        window_s=(hi - lo) / 1e9, steps=steps, devices=devices,
        loop_spans_ms=spans_ms,
    )
