"""What the compiler was asked for and what it built: times, planned
memory, kernels and collectives of one compiled step.

Copied from ``chip_smoke.py`` (PR 22), which proved these readings on the
chip; the smoke keeps its own copy, the benchmark must not move when the
program's does.
"""

from __future__ import annotations

import re
import time

import jax

from .trace import COLLECTIVE_OPS as COLLECTIVES

_PALLAS = 'custom_call_target="tpu_custom_call"'
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


class CompileCounter:
    """Programs that went to the backend compiler or the persistent cache,
    and how the cache answered (``jax.monitoring`` events): ``since_start``
    counts from the counter's creation, ``take()`` since the last take."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache":
            "compile_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        self.since_start = dict.fromkeys(self._EVENTS.values(), 0)
        self._taken = dict(self.since_start)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_):
        key = self._EVENTS.get(name)
        if key is not None:
            self.since_start[key] += 1

    def take(self) -> dict:
        out = {k: v - self._taken[k] for k, v in self.since_start.items()}
        self._taken = dict(self.since_start)
        return out


def instruction_names(hlo: str, marker: str) -> list:
    """Names of the HLO instructions whose line holds ``marker``."""
    names = []
    for line in hlo.splitlines():
        if marker in line:
            m = _INSTRUCTION.match(line)
            if m:
                names.append(m.group(1))
    return names


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_labels(hlo: str) -> dict:
    """HLO instruction name -> the ``op_name`` the compiler kept for it
    (the JAX primitive and the module path it came from), so the trace's
    ``fusion.123`` can be printed with where it comes from."""
    labels = {}
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found = _OP_NAME.search(line)
            if found:
                labels[m.group(1)] = found.group(1)
    return labels


def pallas_call_names(hlo: str) -> list:
    """The compiled Mosaic kernels: the device trace names their events
    after these instructions."""
    return instruction_names(hlo, _PALLAS)


_COLLECTIVE_CALL = re.compile(
    r" (?:%s)(?:-start|-done)?\(" % "|".join(COLLECTIVES)
)


def collective_names(hlo: str) -> list:
    names = []
    for line in hlo.splitlines():
        if _COLLECTIVE_CALL.search(line):
            m = _INSTRUCTION.match(line)
            if m:
                names.append(m.group(1))
    return names


def count_pallas_calls(hlo: str) -> int:
    return hlo.count(_PALLAS)


def count_collectives(stablehlo: str, hlo: str) -> dict:
    """``requested`` is what the program hands the compiler (StableHLO: the
    framework's bucket policy), ``compiled`` what the compiled HLO holds
    after the compiler's own lowering (an asynchronous pair counts once, at
    its ``-start``; an op fused into several consumers is printed, and
    counted, once per consumer)."""
    return {
        "requested": {
            op: stablehlo.count(f"stablehlo.{op.replace('-', '_')}")
            for op in COLLECTIVES
        },
        "compiled": {
            op: len(re.findall(rf" {op}(?:-start)?\(", hlo))
            for op in COLLECTIVES
        },
    }


def planned_bytes(mem) -> dict:
    """The compiled step's plan on one device. ``peak`` is what must be
    resident while it runs: arguments and outputs, less the outputs that
    alias (donated) arguments, plus the temporaries."""
    plan = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
    }
    plan["peak_bytes"] = (
        plan["argument_bytes"] + plan["output_bytes"] - plan["alias_bytes"]
        + plan["temp_bytes"]
    )
    return plan


def lower_and_compile(lower, counter: CompileCounter) -> dict:
    """Trace + lower, then compile, timed apart: the persistent cache can
    only shorten the second."""
    counter.take()
    t0 = time.perf_counter()
    lowered = lower()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    hlo = compiled.as_text()
    return {
        "lower_s": t1 - t0,
        "compile_s": t2 - t1,
        "cache": counter.take(),
        "plan": planned_bytes(compiled.memory_analysis()),
        "pallas_calls": count_pallas_calls(hlo),
        "pallas_call_names": pallas_call_names(hlo),
        "collective_names": collective_names(hlo),
        "labels": instruction_labels(hlo),
        "collectives": count_collectives(lowered.as_text(), hlo),
    }
