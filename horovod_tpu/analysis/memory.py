"""Static per-device HBM memory planner.

The reference sizes its fusion buffer ahead of execution
(``csrc/fusion_buffer.cc``) but discovers every other byte of its memory
envelope empirically, at runtime, on real accelerators. Here the whole
train step is ONE traced SPMD program, so the per-device high-water mark
is computable **statically** from the jaxpr, on a zero-device CPU host —
the resident-bytes twin of the wire-bytes accounting the trace-time
linter already owns.

Model
=====

:func:`plan_traced` traces the step (or takes a pre-traced jaxpr),
descends through the jit/``shard_map`` shells to the **per-device body**
— where batch leaves are the 1/N slice and ZeRO-1 / EF ``FlatBuckets``
avals are the 1/N shard, so world-size effects need no special casing —
then:

1. **linearizes** the body by recursively inlining call-like equations
   (``pjit``, ``remat2``/``checkpoint``, ``custom_jvp/vjp``, …) and
   control flow (``scan``/``while`` bodies once — per-iteration
   intermediates are reused across iterations; ``cond`` branches
   sequentially — their temporaries never coexist, so a time-max over
   the sequence IS the max over branches);
2. assigns every value a **buffer** ``[born, last-use]`` lifetime
   (program outputs live to the end) and sweeps the timeline — classic
   linear-scan — for the peak sum of live bytes. Differentiated
   ``remat2`` bodies are walked in **demand order** (each recompute
   equation lands just before its first consumer, the way XLA
   schedules rematerialized chains — see
   :meth:`_Linearizer._walk_demand`), so residual-anchored recompute
   prices per backward segment instead of all at the region head;
3. models **donation** with the same greedy aval matcher XLA (and
   ``rules.rule_donation``) applies: a donated input with an aliasable
   output and no read after the update shares ONE allocation with it.

Because the walk happens on the *traced* program, the expensive
modeling is free: the remat policy decides which residuals flow from
forward to backward (so ``full < dots_saveable < none`` activation
bytes emerges from the trace), ``accum_steps`` shows up as the rolled
microbatch ``scan`` plus the peeled last backward, and the packed
fusion / quantized wire buffers are ordinary intermediates feeding
collectives.

What is counted: every array the traced program materializes, at aval
payload size, per device. What is NOT counted: XLA fusion (intermediates
the compiler never materializes — the estimate is an upper bound on a
fully-materialized schedule), layout padding, compiler scratch, and the
runtime's fixed overhead (framework + executable buffers). The declared
contract is *relative* fidelity — donation / remat / sharding / world
deltas — plus an absolute resident-bytes check within
``HVDTPU_MEMPLAN_TOLERANCE`` (:func:`compare_to_measured`, held by
``tests/test_memplan.py``).

Surfaces: lint rules ``oom-risk`` / ``donation-missed-reuse`` /
``peak-regression`` (:mod:`.rules`), ``step.memplan(state, batch)``
(:func:`horovod_tpu.parallel.dp.make_train_step`),
``tools/hvdtpu_memplan.py`` (CLI + ZeRO-2/3 projections), and the
``memplan.peak_bytes`` gauge.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from ..utils import env as _env
from .jaxpr_walk import COLLECTIVE_PRIMS, aval_nbytes

# Report categories, in breakdown order. "workspace" absorbs the batch
# slice, step counters, guard scalars and anything unclassified.
CATEGORIES = ("params", "opt_state", "activations", "wire", "workspace")


@dataclasses.dataclass(frozen=True)
class MemoryLintConfig:
    """What the memory rule pass gates against (see
    :func:`~horovod_tpu.analysis.rules.rule_memory`): ``None`` budget /
    baseline leaves the corresponding rule silent."""

    budget_bytes: Optional[int] = None
    baseline_bytes: Optional[int] = None
    baseline_key: str = ""
    donation_threshold: float = 0.05
    regression_tolerance: float = 1.05


class _Buf:
    """One allocation: payload bytes, lifetime, and report category.

    ``group`` links donation-aliased buffers: members share one
    allocation, so live-byte accounting charges the group once.
    """

    __slots__ = ("nbytes", "cls", "label", "born", "last", "group")

    def __init__(self, nbytes: int, cls: str = "activations", label: str = ""):
        self.nbytes = int(nbytes)
        self.cls = cls
        self.label = label
        self.born = -1  # event index that writes it (-1 = program entry)
        self.last = -1  # last event index that reads it
        self.group: Optional["_Buf"] = None  # alias-group representative

    def rep(self) -> "_Buf":
        b = self
        while b.group is not None:
            b = b.group
        return b


@dataclasses.dataclass
class MemoryPlan:
    """Per-device HBM plan for one traced step (see module docstring)."""

    peak_bytes: int
    breakdown: Dict[str, int]  # at-peak live bytes per category (sums to peak)
    resident_bytes: int  # per-device persistent state (params + opt + misc)
    global_state_bytes: int  # OUTER-aval (state, batch) bytes — what
    # ``jax.live_arrays`` reports for the committed state on a CPU host
    params_bytes: int
    opt_state_bytes: int
    batch_bytes: int
    wire_bytes: int  # at-peak live fused/quantized wire buffers
    activation_bytes: int
    donation_saved_bytes: int  # peak(no aliasing) - peak
    undonated_candidates: Tuple[Dict[str, Any], ...]
    world: int
    n_eqns: int
    n_buffers: int
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "peak_bytes": self.peak_bytes,
            "breakdown": dict(self.breakdown),
            "resident_bytes": self.resident_bytes,
            "global_state_bytes": self.global_state_bytes,
            "params_bytes": self.params_bytes,
            "opt_state_bytes": self.opt_state_bytes,
            "batch_bytes": self.batch_bytes,
            "wire_bytes": self.wire_bytes,
            "activation_bytes": self.activation_bytes,
            "donation_saved_bytes": self.donation_saved_bytes,
            "undonated_candidates": [dict(c) for c in self.undonated_candidates],
            "world": self.world,
            "n_eqns": self.n_eqns,
            "n_buffers": self.n_buffers,
            "meta": dict(self.meta),
        }

    def fmt(self) -> str:
        """Human breakdown table (the CLI's per-model block)."""
        lines = [f"peak {_fmt_bytes(self.peak_bytes)}/device"]
        for cat in CATEGORIES:
            b = self.breakdown.get(cat, 0)
            pct = 100.0 * b / self.peak_bytes if self.peak_bytes else 0.0
            lines.append(f"  {cat:<12} {_fmt_bytes(b):>10}  {pct:5.1f}%")
        lines.append(
            f"  {'(donation saves':<12} {_fmt_bytes(self.donation_saved_bytes):>10})"
        )
        return "\n".join(lines)


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024.0
    return f"{n}B"  # pragma: no cover


# -- jaxpr linearization -------------------------------------------------


def _aval_key(aval) -> Tuple:
    return (tuple(getattr(aval, "shape", ())), str(aval.dtype))


class _Event:
    __slots__ = ("reads", "writes", "prim")

    def __init__(self, reads: List[_Buf], writes: List[_Buf], prim: str = ""):
        self.reads = reads
        self.writes = writes
        self.prim = prim


class _Linearizer:
    """Recursive inliner: one flat event list for the whole body."""

    def __init__(self) -> None:
        self.events: List[_Event] = []
        self.env: Dict[int, _Buf] = {}  # id(var) -> buffer
        self.buffers: List[_Buf] = []
        self._mask_memo: Dict[int, Optional[List[bool]]] = {}

    def buf_for(self, var, cls: str = "activations", label: str = "") -> _Buf:
        b = self.env.get(id(var))
        if b is None:
            b = _Buf(aval_nbytes(var.aval), cls, label)
            self.env[id(var)] = b
            self.buffers.append(b)
        return b

    def bind(self, var, buf: _Buf) -> None:
        self.env[id(var)] = buf

    def read_bufs(self, invars) -> List[_Buf]:
        return [
            self.buf_for(v) for v in invars if not isinstance(v, Literal)
        ]

    def emit(self, reads: List[_Buf], writes: List[_Buf], prim: str) -> None:
        self.events.append(_Event(reads, writes, prim))

    # -- walk ------------------------------------------------------------

    def walk(self, jaxpr) -> None:
        for cv in jaxpr.constvars:
            self.buf_for(cv, cls="workspace", label="const")
        for eqn in jaxpr.eqns:
            self._walk_eqn(eqn)

    def _walk_eqn(self, eqn) -> None:
        name = eqn.primitive.name
        if name == "scan":
            self._walk_scan(eqn)
        elif name == "while":
            self._walk_while(eqn)
        elif name == "cond":
            self._walk_cond(eqn)
        else:
            subs = _sub_jaxprs(eqn)
            if subs:
                self._walk_call(eqn, subs, name)
            else:
                reads = self.read_bufs(eqn.invars)
                writes = [self._out_buf(ov, name) for ov in eqn.outvars]
                self.emit(reads, writes, name)

    def _invar_mask(self, eqn) -> Optional[List[bool]]:
        """Which invars the equation actually reads (``None`` = all).

        Call-like equations list every operand even when the sub-jaxpr
        never reads it — the classic case is the tangent-only operand
        of an STE ``custom_jvp_call`` (the dequantized value is computed
        from the int8 payload alone; the full-precision input rides
        along only for the identity tangent). XLA inlines the body and
        DCEs the dead chain feeding such operands, so pricing them as
        live dependencies would be a fiction. Control flow
        (scan/while/cond) stays conservative: all operands count.
        """
        key = id(eqn)
        if key in self._mask_memo:
            return self._mask_memo[key]
        self._mask_memo[key] = None  # default while computing (no cycles)
        mask: Optional[List[bool]] = None
        if eqn.primitive.name not in ("scan", "while", "cond"):
            subs = _sub_jaxprs(eqn)
            if len(subs) == 1:
                sub = subs[0]
                used = self._sub_used_ids(sub)
                mask = [True] * len(eqn.invars)
                ops_idx = [
                    k
                    for k, v in enumerate(eqn.invars)
                    if not isinstance(v, Literal)
                ]
                invars = list(sub.invars)
                n = min(len(ops_idx), len(invars))
                for oi, iv in zip(
                    ops_idx[len(ops_idx) - n :], invars[len(invars) - n :]
                ):
                    mask[oi] = id(iv) in used
                if all(mask):
                    mask = None
        self._mask_memo[key] = mask
        return mask

    def _sub_used_ids(self, jaxpr) -> Set[int]:
        """Ids of the jaxpr's vars transitively needed by its outputs
        (or by collectives — effects stay live): a reverse DCE pass."""
        live = {
            id(v) for v in jaxpr.outvars if not isinstance(v, Literal)
        }
        for eqn in reversed(jaxpr.eqns):
            needed = any(id(ov) in live for ov in eqn.outvars) or (
                eqn.primitive.name in COLLECTIVE_PRIMS
            )
            if not needed:
                continue
            m = self._invar_mask(eqn)
            for k, v in enumerate(eqn.invars):
                if isinstance(v, Literal):
                    continue
                if m is None or m[k]:
                    live.add(id(v))
        return live

    def _walk_demand(self, jaxpr) -> None:
        """Walk a differentiated ``remat2`` body in demand order.

        The traced order of such a region is (recompute everything;
        then the whole backward), so an in-order sweep would charge
        every rematerialized intermediate at the region head — erasing
        exactly the savings remat policies and int8 activation storage
        exist for. XLA schedules each recompute chain next to its
        consumer instead; model that by emitting each equation just
        before its first transitive consumer: iterate the region's
        output-producing equations in traced order (the backward runs
        last-block-first, so each block's grads demand that block's
        recompute — and only that block's, when the recompute is
        anchored on a saved residual rather than chained to the start).
        """
        for cv in jaxpr.constvars:
            self.buf_for(cv, cls="workspace", label="const")
        eqns = jaxpr.eqns
        produced_by: Dict[int, int] = {}
        for i, e in enumerate(eqns):
            for ov in e.outvars:
                produced_by[id(ov)] = i
        emitted = [False] * len(eqns)

        def emit_with_deps(root: int) -> None:
            stack = [(root, False)]
            while stack:
                i, ready = stack.pop()
                if emitted[i]:
                    continue
                if ready:
                    emitted[i] = True
                    self._walk_eqn(eqns[i])
                    continue
                stack.append((i, True))
                m = self._invar_mask(eqns[i])
                for k, v in enumerate(eqns[i].invars):
                    if isinstance(v, Literal):
                        continue
                    if m is not None and not m[k]:
                        continue
                    j = produced_by.get(id(v))
                    if j is not None and not emitted[j]:
                        stack.append((j, False))

        roots = sorted(
            {
                produced_by[id(ov)]
                for ov in jaxpr.outvars
                if not isinstance(ov, Literal) and id(ov) in produced_by
            }
            | {
                i
                for i, e in enumerate(eqns)
                if e.primitive.name in COLLECTIVE_PRIMS
            }
        )
        for r in roots:
            emit_with_deps(r)
        # Anything never demanded is dead inside the region — commonly
        # the tangent-only chains feeding STE custom_jvp operands —
        # and XLA's DCE drops it, so the plan does too.

    def _out_buf(self, outvar, prim: str) -> _Buf:
        cls = "wire" if prim in COLLECTIVE_PRIMS else "activations"
        b = _Buf(aval_nbytes(outvar.aval), cls)
        self.env[id(outvar)] = b
        self.buffers.append(b)
        return b

    def _walk_call(self, eqn, subs, name) -> None:
        """Inline a call-like equation (pjit / remat2 / custom_* / …):
        operand buffers map to the sub-jaxpr's trailing invars (leading
        extras on either side are consts, like jaxpr_walk's taint map).
        Only operands the sub-jaxpr actually reads count as reads —
        tangent-only custom_jvp operands don't pin their producers."""
        mask = self._invar_mask(eqn)
        if mask is None:
            used_invars = eqn.invars
        else:
            used_invars = [
                v for v, u in zip(eqn.invars, mask) if u
            ]
        operands = self.read_bufs(used_invars)
        sub = subs[0]
        ops = [v for v in eqn.invars if not isinstance(v, Literal)]
        invars = list(sub.invars)
        n = min(len(ops), len(invars))
        for op, iv in zip(ops[len(ops) - n :], invars[len(invars) - n :]):
            self.bind(iv, self.buf_for(op))
        if name == "remat2" and eqn.params.get("differentiated", False):
            self._walk_demand(sub)
        else:
            self.walk(sub)
        out_bufs = [
            self.buf_for(ov) if not isinstance(ov, Literal) else None
            for ov in sub.outvars
        ]
        for ov, b in zip(eqn.outvars, out_bufs):
            if b is not None:
                self.bind(ov, b)
            else:  # literal output: tiny fresh buffer
                self._out_buf(ov, name)
        # Close the region: operands stay live at least to the call end.
        self.emit(operands, [], name)

    def _walk_scan(self, eqn) -> None:
        sub = eqn.params["jaxpr"].jaxpr
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        operands = [v for v in eqn.invars]
        op_bufs = self.read_bufs(operands)
        # consts + init carries map through; xs map as per-iteration
        # slices (the body aval IS the slice).
        for op, iv in zip(operands[: n_consts + n_carry],
                          sub.invars[: n_consts + n_carry]):
            if not isinstance(op, Literal):
                self.bind(iv, self.buf_for(op))
        slice_bufs = []
        for iv in sub.invars[n_consts + n_carry :]:
            b = _Buf(aval_nbytes(iv.aval), "activations")
            self.buffers.append(b)
            self.bind(iv, b)
            slice_bufs.append(b)
        # Stacked ys allocate up front and outlive the loop.
        y_bufs = [
            self._out_buf(ov, "scan") for ov in eqn.outvars[n_carry:]
        ]
        self.emit(op_bufs, y_bufs + slice_bufs, "scan")
        self.walk(sub)
        # Final carries alias the body's last carry-out values.
        for ov, bv in zip(eqn.outvars[:n_carry], sub.outvars[:n_carry]):
            if isinstance(bv, Literal):
                self._out_buf(ov, "scan")
            else:
                self.bind(ov, self.buf_for(bv))
        self.emit(op_bufs + y_bufs, [], "scan")

    def _walk_while(self, eqn) -> None:
        cond_n = eqn.params["cond_nconsts"]
        body_n = eqn.params["body_nconsts"]
        cond_j = eqn.params["cond_jaxpr"].jaxpr
        body_j = eqn.params["body_jaxpr"].jaxpr
        op_bufs = self.read_bufs(eqn.invars)
        carry = eqn.invars[cond_n + body_n :]
        for op, iv in zip(eqn.invars[:cond_n], cond_j.invars[:cond_n]):
            if not isinstance(op, Literal):
                self.bind(iv, self.buf_for(op))
        for op, iv in zip(carry, cond_j.invars[cond_n:]):
            if not isinstance(op, Literal):
                self.bind(iv, self.buf_for(op))
        for op, iv in zip(eqn.invars[cond_n : cond_n + body_n],
                          body_j.invars[:body_n]):
            if not isinstance(op, Literal):
                self.bind(iv, self.buf_for(op))
        for op, iv in zip(carry, body_j.invars[body_n:]):
            if not isinstance(op, Literal):
                self.bind(iv, self.buf_for(op))
        self.emit(op_bufs, [], "while")
        self.walk(cond_j)
        self.walk(body_j)
        for ov, bv in zip(eqn.outvars, body_j.outvars):
            if isinstance(bv, Literal):
                self._out_buf(ov, "while")
            else:
                self.bind(ov, self.buf_for(bv))
        self.emit(op_bufs, [], "while")

    def _walk_cond(self, eqn) -> None:
        op_bufs = self.read_bufs(eqn.invars)
        self.emit(op_bufs, [], "cond")
        last_outs = None
        for branch in eqn.params["branches"]:
            sub = branch.jaxpr
            ops = [v for v in eqn.invars[1:] if not isinstance(v, Literal)]
            invars = list(sub.invars)
            n = min(len(ops), len(invars))
            for op, iv in zip(ops[len(ops) - n :], invars[len(invars) - n :]):
                self.bind(iv, self.buf_for(op))
            self.walk(sub)
            last_outs = sub.outvars
        for ov, bv in zip(eqn.outvars, last_outs or []):
            if isinstance(bv, Literal):
                self._out_buf(ov, "cond")
            else:
                self.bind(ov, self.buf_for(bv))
        self.emit(op_bufs, [], "cond")


def _sub_jaxprs(eqn) -> List[Any]:
    subs = []
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if isinstance(item, ClosedJaxpr):
                subs.append(item.jaxpr)
            elif isinstance(item, Jaxpr):
                subs.append(item)
    return subs


def _descend_to_body(jaxpr, tag_rows: List[List]):
    """Descend through single-equation call shells (the jit pjit shell,
    the ``shard_map`` wrapper) to the per-device body, with per-invar tag
    rows (donated flag, category, label) following positionally — the
    planner twin of ``rules._descend_donation``. Crucially the BODY
    avals are per-device (batch slice, 1/N ``FlatBuckets`` shards), so
    everything downstream is already per-device accounting."""
    while len(jaxpr.eqns) == 1:
        eqn = jaxpr.eqns[0]
        produced = {id(v) for v in eqn.outvars}
        if not all(
            isinstance(v, Literal) or id(v) in produced
            for v in jaxpr.outvars
        ):
            break
        subs = _sub_jaxprs(eqn)
        if len(subs) != 1:
            break
        sub = subs[0]
        if len(eqn.invars) != len(sub.invars):
            break
        tag_of = {
            id(v): row
            for v, row in zip(jaxpr.invars, zip(*tag_rows))
        }
        new_rows: List[List] = [[] for _ in tag_rows]
        defaults = (False, "workspace", "")
        for op in eqn.invars:
            row = tag_of.get(id(op), defaults[: len(tag_rows)])
            for dst, val in zip(new_rows, row):
                dst.append(val)
        jaxpr, tag_rows = sub, new_rows
    return jaxpr, tag_rows


# -- the sweep -----------------------------------------------------------


def _assign_lifetimes(
    buffers: Sequence[_Buf], events: Sequence[_Event],
    out_bufs: Sequence[_Buf],
) -> int:
    """(Re)compute buffer lifetimes for one event order: born at the
    writing event, last at the last reading event, program outputs live
    to the horizon. Returns the horizon (event count)."""
    for b in buffers:
        b.born = -1
        b.last = -1
    for t, ev in enumerate(events):
        for b in ev.writes:
            if b.born < 0:
                b.born = t
        for b in ev.reads:
            b.last = max(b.last, t)
    horizon = len(events)
    for b in out_bufs:
        b.last = horizon
    return horizon


def _sweep(
    buffers: Sequence[_Buf], events: Sequence[_Event], horizon: int
) -> Tuple[int, int, Dict[str, int]]:
    """Linear scan over buffer lifetimes: returns ``(peak_bytes,
    peak_time, at-peak per-category breakdown)``. Alias groups are
    charged once, at the max member size, over the union lifetime."""
    groups: Dict[int, Dict[str, Any]] = {}
    for b in buffers:
        if b.last < b.born:
            continue  # never read and not an output: zero-cost
        rep = b.rep()
        g = groups.get(id(rep))
        if g is None:
            g = {"born": b.born, "last": b.last, "bytes": b.nbytes,
                 "cls": b.cls}
            groups[id(rep)] = g
        else:
            g["born"] = min(g["born"], b.born)
            g["last"] = max(g["last"], b.last)
            g["bytes"] = max(g["bytes"], b.nbytes)
    delta = [0] * (horizon + 3)
    for g in groups.values():
        delta[g["born"] + 1] += g["bytes"]
        delta[g["last"] + 2] -= g["bytes"]
    peak, peak_t, live = 0, -1, 0
    for t in range(horizon + 2):
        live += delta[t]
        if live > peak:
            peak, peak_t = live, t - 1
    breakdown = {c: 0 for c in CATEGORIES}
    for g in groups.values():
        if g["born"] <= peak_t <= g["last"]:
            cls = g["cls"] if g["cls"] in breakdown else "workspace"
            breakdown[cls] += g["bytes"]
    return peak, peak_t, breakdown


def _expand_arg_classes(args: Tuple, arg_classes: Optional[Sequence[str]]):
    """Per-leaf category list matching ``jax.make_jaxpr``'s invar order.
    ``TrainState``-shaped first args classify their components; plain
    trees default to params-then-workspace."""
    classes: List[str] = []
    for i, arg in enumerate(args):
        if hasattr(arg, "params") and hasattr(arg, "opt_state"):
            comps = (
                ("params", arg.params),
                ("opt_state", arg.opt_state),
                ("workspace", getattr(arg, "step", None)),
                ("workspace", getattr(arg, "extra", None)),
                ("workspace", getattr(arg, "guard", None)),
            )
            for cls, comp in comps:
                classes.extend([cls] * len(jax.tree_util.tree_leaves(comp)))
            continue
        if arg_classes is not None and i < len(arg_classes):
            cls = arg_classes[i]
        else:
            cls = "params" if i == 0 else "workspace"
        classes.extend([cls] * len(jax.tree_util.tree_leaves(arg)))
    return classes


def plan_traced(
    fn,
    args: Tuple,
    *,
    donate_argnums: Sequence[int] = (),
    arg_classes: Optional[Sequence[str]] = None,
    world: int = 1,
    jaxpr=None,
    meta: Optional[Dict[str, Any]] = None,
) -> MemoryPlan:
    """Plan one traced step (see module docstring).

    ``args`` may be abstract (``ShapeDtypeStruct`` / ``jax.eval_shape``
    pytrees) — nothing executes. ``jaxpr`` skips re-tracing when the
    caller already traced (``harness``'s per-variant cache).
    ``arg_classes`` labels each top-level arg's leaves for the breakdown
    (``TrainState`` args self-classify).
    """
    closed = jaxpr if jaxpr is not None else jax.make_jaxpr(fn)(*args)
    outer = getattr(closed, "jaxpr", closed)
    global_state_bytes = sum(
        aval_nbytes(v.aval) for v in outer.invars
    )

    classes = _expand_arg_classes(args, arg_classes)
    donate = frozenset(donate_argnums)
    donated: List[bool] = []
    labels: List[str] = []
    for i, arg in enumerate(args):
        n = len(jax.tree_util.tree_leaves(arg))
        donated.extend([i in donate] * n)
        labels.extend([f"arg{i}[{j}]" for j in range(n)])
    if len(classes) != len(outer.invars):
        # Tracing may close over consts or flatten differently; pad
        # conservatively rather than refuse to plan.
        classes = (classes + ["workspace"] * len(outer.invars))[
            : len(outer.invars)
        ]
        donated = (donated + [False] * len(outer.invars))[: len(outer.invars)]
        labels = (labels + [""] * len(outer.invars))[: len(outer.invars)]

    body, (donated, classes, labels) = _descend_to_body(
        outer, [donated, classes, labels]
    )

    lin = _Linearizer()
    for iv, cls, label in zip(body.invars, classes, labels):
        lin.buf_for(iv, cls=cls, label=label)
    lin.walk(body)

    # Lifetimes: born at writing event, last at last reading event;
    # program outputs live to the horizon.
    out_bufs = [
        lin.buf_for(v)
        for v in body.outvars
        if not isinstance(v, Literal)
    ]
    events = lin.events
    horizon = _assign_lifetimes(lin.buffers, events, out_bufs)
    in_bufs = [lin.buf_for(iv) for iv in body.invars]
    real_last = {id(b): b.last for b in in_bufs}  # pre-pin last READ

    # Donation-off counterfactual first: EVERY input buffer is held by
    # the caller for the whole call (XLA may neither free nor reuse a
    # non-donated buffer), outputs allocate fresh.
    for b in in_bufs:
        b.last = horizon
    peak_no_donation, _, _ = _sweep(lin.buffers, events, horizon)

    # Donation aliasing: greedy in-order aval match (XLA's pairing), no
    # aliasing when the input is read after the aliased output is born.
    # A donated input is released: matched pairs share one allocation;
    # unmatched (donation-dropped) ones still free at their last read.
    unmatched = list(out_bufs)
    unmatched_vars = [
        v for v in body.outvars if not isinstance(v, Literal)
    ]
    candidates: List[Dict[str, Any]] = []
    for iv, ib, is_don, cls, label in zip(
        body.invars, in_bufs, donated, classes, labels
    ):
        match_i = next(
            (
                k
                for k, ov in enumerate(unmatched_vars)
                if _aval_key(ov.aval) == _aval_key(iv.aval)
            ),
            None,
        )
        if match_i is None:
            if is_don:  # donation-dropped: freed after the last read
                ib.last = max(0, real_last[id(ib)])
            continue
        ob = unmatched.pop(match_i)
        unmatched_vars.pop(match_i)
        if ob is ib:
            continue  # passthrough: trivially aliased
        if real_last[id(ib)] > ob.born >= 0:
            continue  # read-after-update: XLA cannot alias (stays pinned)
        if is_don:
            ob.group = ib  # one allocation, union lifetime (to horizon)
        else:
            candidates.append(
                {"label": label, "class": cls, "bytes": ib.nbytes,
                 "buf": ib, "out": ob}
            )

    peak, peak_t, breakdown = _sweep(lin.buffers, events, horizon)

    # Undonated candidates: donating would merge the input with its
    # matched output (saving its bytes while both are live) or at least
    # free it after its last real read. Either way the peak drops by
    # the buffer's bytes iff the buffer's presence at the peak instant
    # is removable: the matched output is also live there, or the last
    # real read precedes the peak.
    undonated = tuple(
        {
            "label": c["label"],
            "class": c["class"],
            "bytes": c["bytes"],
            "saving_bytes": min(c["bytes"], c["out"].nbytes),
        }
        for c in candidates
        if (c["out"].born <= peak_t <= c["out"].last)
        or real_last[id(c["buf"])] < peak_t
    )

    params_b = sum(
        lin.buf_for(iv).nbytes
        for iv, cls in zip(body.invars, classes)
        if cls == "params"
    )
    opt_b = sum(
        lin.buf_for(iv).nbytes
        for iv, cls in zip(body.invars, classes)
        if cls == "opt_state"
    )
    batch_b = sum(
        lin.buf_for(iv).nbytes
        for iv, cls, label in zip(body.invars, classes, labels)
        if cls == "workspace" and label.startswith("arg1")
    )
    return MemoryPlan(
        peak_bytes=peak,
        breakdown=breakdown,
        resident_bytes=params_b + opt_b,
        global_state_bytes=global_state_bytes,
        params_bytes=params_b,
        opt_state_bytes=opt_b,
        batch_bytes=batch_b,
        wire_bytes=breakdown.get("wire", 0),
        activation_bytes=breakdown.get("activations", 0),
        donation_saved_bytes=max(0, peak_no_donation - peak),
        undonated_candidates=undonated,
        world=world,
        n_eqns=len(events),
        n_buffers=len(lin.buffers),
        meta=dict(meta or {}),
    )


# -- projections (ZeRO-2/3 what-ifs, costed before they exist) -----------


def project_sharding(plan: MemoryPlan, world: Optional[int] = None) -> Dict:
    """Analytic ZeRO-stage projections from one planned step: what the
    per-device peak becomes when gradients (ZeRO-2) and parameters
    (ZeRO-3) shard 1/N like the ZeRO-1 optimizer state already does.
    Gradient bytes are approximated by the params footprint (one grad
    per param, same dtype) and activations are held fixed — the honest
    first-order model for pure data parallelism."""
    n = world or plan.world
    grad_b = plan.params_bytes  # transient, currently full-size per device
    zero2 = plan.peak_bytes - grad_b * (n - 1) // n
    zero3 = zero2 - plan.params_bytes * (n - 1) // n
    return {
        "world": n,
        "zero1_peak_bytes": plan.peak_bytes,
        "zero2_peak_bytes": max(0, zero2),
        "zero3_peak_bytes": max(0, zero3),
        "grad_bytes_assumed": grad_b,
    }


# -- measurement (predicted-vs-actual) -----------------------------------


def live_array_bytes(exclude_ids: Optional[Set[int]] = None) -> int:
    """Total logical payload bytes of every live ``jax.Array`` in the
    process, minus ``exclude_ids`` (ids snapshotted before the run) —
    the CPU-host "actual" the planner's ``global_state_bytes`` is gated
    against. Logical bytes: a replicated array counts once, matching the
    planner's accounting."""
    excl = exclude_ids or set()
    total = 0
    for a in jax.live_arrays():
        if id(a) in excl:
            continue
        total += int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
    return total


def snapshot_live_ids() -> Set[int]:
    return {id(a) for a in jax.live_arrays()}


def compare_to_measured(
    plan: MemoryPlan, measured: int, source: str,
    tolerance: Optional[float] = None,
) -> Dict[str, Any]:
    """The drift gate: predicted vs actual with a relative-error
    tolerance (``HVDTPU_MEMPLAN_TOLERANCE`` default). ``source`` says
    what ``measured`` is: ``live_arrays`` (the post-step
    ``jax.live_arrays`` total) compares resident state; ``device_peak``
    (a step's own ``peak_bytes_in_use`` delta) compares the modeled peak,
    an upper bound on the compiled schedule, so only the
    *under*-prediction side is a hard failure there;
    ``device_peak_stale`` (the lifetime peak predates the step) gives no
    verdict."""
    if tolerance is None:
        tolerance = _env.memplan_tolerance()
    predicted = (
        plan.global_state_bytes if source == "live_arrays" else plan.peak_bytes
    )
    ratio = predicted / measured if measured else float("inf")
    if source == "device_peak":
        ok = predicted >= measured * (1.0 - tolerance)
    elif source == "device_peak_stale":
        # Lifetime peak predates the measured step: no verdict.
        ok = None
    else:
        ok = abs(ratio - 1.0) <= tolerance
    return {
        "predicted_peak_bytes": plan.peak_bytes,
        "predicted_resident_bytes": plan.global_state_bytes,
        "measured_bytes": int(measured),
        "source": source,
        "ratio": round(ratio, 4),
        "tolerance": tolerance,
        "ok": None if ok is None else bool(ok),
        "breakdown": dict(plan.breakdown),
    }
