"""What a compiled step does with its collectives: read the scheduled HLO.

A compiled module's entry computation is scheduled (``is_scheduled=true``),
so the order of its instructions is the order of execution. A collective
the chip waits through is one instruction (`` all-reduce(``); one it can
hide is a pair with other work between the halves. The TPU compiler
writes the pair as two custom fusions, ``async-collective-start[.N]`` and
``async-collective-done[.N]``, whose called computations hold the
collective and an ``AsyncCollectiveStart`` / ``AsyncCollectiveDone``
custom call; plain XLA writes ``all-reduce-start`` / ``all-reduce-done``.
:func:`collective_schedule` reads both, from the text alone: nothing is
compiled or run here.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

COLLECTIVE_KINDS = (
    "all-reduce", "reduce-scatter", "all-gather", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_SHAPE = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_DTYPE_BYTES))
# name = <result type> opcode(operands...), attributes
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([a-z][\w\-]*)\((.*)$"
)
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COLLECTIVE = re.compile(r"(%s)(-start|-done)?$" % "|".join(COLLECTIVE_KINDS))
_MATMUL = re.compile(r"\s(?:convolution|dot)\(")
_KERNEL = 'custom_call_target="tpu_custom_call"'


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _computations(hlo_text: str):
    """``(bodies, entry)``: every computation's lines by its name, and the
    entry computation's name."""
    bodies: Dict[str, List[str]] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = m.group(2)
                bodies[cur] = []
                if m.group(1):
                    entry = cur
        elif line.startswith("}"):
            cur = None
        else:
            bodies[cur].append(line)
    return bodies, entry


def _inner_collective(lines):
    """The collective instruction a called computation wraps:
    ``(kind, bytes, n_operands, op_name)`` or None."""
    for line in lines:
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        kind = _COLLECTIVE.match(m.group(3))
        if kind and kind.group(2) != "-done":
            label = _OP_NAME.search(line)
            return (
                kind.group(1), _shape_bytes(m.group(2)),
                len(_OPERAND.findall(m.group(4).split(")", 1)[0])),
                label.group(1) if label else "",
            )
    return None


def collective_schedule(hlo_text: str, scope: Optional[str] = "hvd_reduce"):
    """Synchronous and asynchronous collectives of a compiled step.

    ``hlo_text`` is ``compiled.as_text()`` of a scheduled module. ``scope``
    keeps the collectives whose ``op_name`` holds it (the gradient
    exchange names itself ``hvd_reduce``; the loss average and the guard's
    scalars do not count); ``None`` keeps all of them.

    Returns a dict: ``entry_instructions``; ``sync`` and ``async``, lists
    in schedule order of ``{"name", "kind", "index", "bytes",
    "operands"}``, an asynchronous pair with ``done``, ``done_index`` and
    what the schedule puts between its halves: ``matmuls_between``
    (fusions that hold a ``convolution`` or ``dot``), ``updates_between``
    (fusions of the ``hvd_update`` scope), ``kernels_between`` (Mosaic
    calls) and ``between``, every instruction; and the totals
    ``n_sync`` / ``sync_bytes`` / ``n_async`` / ``async_bytes`` /
    ``async_bytes_share`` (0.0 where there is no collective at all).
    ``bytes`` is the collective's result on one device.
    """
    bodies, entry = _computations(hlo_text)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    # what each called computation holds, found once
    holds = {
        name: {
            "matmul": any(_MATMUL.search(line) for line in lines),
            "update": any("hvd_update" in line for line in lines),
            "-start": any("AsyncCollectiveStart" in line for line in lines),
            "-done": any("AsyncCollectiveDone" in line for line in lines),
        }
        for name, lines in bodies.items()
    }
    nothing = dict.fromkeys(("matmul", "update", "-start", "-done"), False)
    rows = []  # (name, opcode, operands, called, line, result type)
    for line in bodies[entry]:
        m = _INSTRUCTION.match(line)
        if m:
            called = _CALLS.search(line)
            rows.append((
                m.group(1), m.group(3), _OPERAND.findall(m.group(4)),
                called.group(1) if called else None, line, m.group(2),
            ))
    index = {row[0]: i for i, row in enumerate(rows)}

    def work(i):
        """Which of matmul / update / kernel the instruction at ``i`` is."""
        _, opcode, _, called, line, _ = rows[i]
        if opcode == "custom-call" and _KERNEL in line:
            return "kernel"
        inside = holds.get(called, nothing)
        if opcode in ("convolution", "dot") or (
            opcode == "fusion" and inside["matmul"]
        ):
            return "matmul"
        # a multi-output fusion carries no op_name of its own: look inside
        label = _OP_NAME.search(line)
        if opcode == "fusion" and (
            inside["update"] or (label and "hvd_update" in label.group(1))
        ):
            return "update"
        return None

    starts: Dict[int, dict] = {}
    sync: List[dict] = []
    pairs: List[dict] = []
    for i, (name, opcode, operands, called, line, result) in enumerate(rows):
        found, half = None, None
        direct = _COLLECTIVE.match(opcode)
        if direct:
            half = direct.group(2) or ""
            label = _OP_NAME.search(line)
            found = (
                direct.group(1), _shape_bytes(result),
                len([o for o in operands if o in index]),
                label.group(1) if label else "",
            )
        elif opcode == "async-start" or (
            opcode == "fusion" and holds.get(called, nothing)["-start"]
        ):
            found, half = _inner_collective(bodies.get(called, ())), "-start"
        elif opcode == "async-done" or (
            opcode == "fusion" and holds.get(called, nothing)["-done"]
        ):
            half = "-done"
        if half == "-done":
            # the compiler names a pair ``...-start.N`` / ``...-done.N``
            # (its operands do not say: under collective/compute overlap
            # they pass through the fusions between the two)
            twin = index.get(name.replace("-done", "-start", 1))
            if twin in starts:
                rec = starts.pop(twin)
                rec.update(done=name, done_index=i)
                pairs.append(rec)
            continue
        if found is None:
            continue
        kind, nbytes, n_operands, label = found
        if scope is not None and scope not in label:
            continue
        rec = {"name": name, "kind": kind, "index": i, "bytes": nbytes,
               "operands": n_operands}
        if half == "-start":
            if opcode.endswith("-start") and opcode != "async-start":
                # all-reduce-start's result carries operand and result
                rec["bytes"] = nbytes // 2
            starts[i] = rec
        else:
            sync.append(rec)
    for rec in pairs:
        kinds = [work(i) for i in range(rec["index"] + 1, rec["done_index"])]
        rec["between"] = len(kinds)
        rec["matmuls_between"] = kinds.count("matmul")
        rec["updates_between"] = kinds.count("update")
        rec["kernels_between"] = kinds.count("kernel")
    pairs.sort(key=lambda r: r["index"])
    sync_bytes = sum(r["bytes"] for r in sync)
    async_bytes = sum(r["bytes"] for r in pairs)
    total = sync_bytes + async_bytes
    kernels = [i for i in range(len(rows)) if work(i) == "kernel"]
    return {
        "entry_instructions": len(rows),
        "last_kernel_index": kernels[-1] if kernels else None,
        "sync": sync,
        "async": pairs,
        "n_sync": len(sync),
        "sync_bytes": sync_bytes,
        "n_async": len(pairs),
        "async_bytes": async_bytes,
        "async_bytes_share": async_bytes / total if total else 0.0,
    }

