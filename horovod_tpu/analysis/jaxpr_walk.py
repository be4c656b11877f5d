"""Generic jaxpr traversal for the SPMD linter.

One recursive walk over a traced step produces everything the rule
passes need:

* every **collective equation** (psum / reduce-scatter / all-gather /
  all-to-all / ppermute / pmax / pmin) with its axis names, operand and
  result avals, global preorder position and nesting path;
* the **control-flow context** of each collective — which
  ``cond``/``while``/``scan`` equations enclose it, and whether any of
  those are *rank-dependent*, i.e. their predicate/operands are tainted
  by ``axis_index`` (the static signature of rank-divergent control
  flow, the one way an SPMD program deadlocks on real hardware);
* every **loop carry** of a ``while``/``scan`` body (for the precision
  pass's pure-accumulator check).

The walker is deliberately structural: any equation parameter that is a
``Jaxpr``/``ClosedJaxpr`` (or list/tuple of them) is descended into, so
``pjit``, ``shard_map``, ``remat``, ``custom_jvp/vjp`` and future
call-like primitives are handled without per-primitive code. Taint is
propagated positionally into sub-jaxprs for the primitives where the
operand↔invar mapping matters (``cond``/``while``/``scan``) and by a
conservative suffix alignment everywhere else.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

# Cross-device communication primitives by jaxpr name. ``psum_bind`` etc.
# never appear in jaxprs; these are the canonical post-trace names.
COLLECTIVE_PRIMS = frozenset(
    {
        "psum",
        "psum_invariant",
        "reduce_scatter",
        "all_gather",
        "all_gather_invariant",
        "all_to_all",
        "ppermute",
        "pmax",
        "pmin",
        "pgather",
    }
)
# Collectives that REDUCE (arithmetic over the axis — where low-precision
# wire dtypes round the result). all_gather/ppermute only move bytes.
REDUCING_COLLECTIVE_PRIMS = frozenset(
    {"psum", "psum_invariant", "reduce_scatter", "pmax", "pmin"}
)
CONTROL_FLOW_PRIMS = frozenset({"cond", "while", "scan"})

_LOW_PRECISION = ("bfloat16", "float16")


def is_low_precision(aval) -> bool:
    return getattr(aval, "dtype", None) is not None and str(
        aval.dtype
    ) in _LOW_PRECISION


def aval_nbytes(aval) -> int:
    """Payload bytes of one aval (shape/dtype metadata only)."""
    size = 1
    for d in getattr(aval, "shape", ()):  # scalars -> 1
        size *= int(d)
    return size * aval.dtype.itemsize


def _axis_names(eqn) -> Tuple[str, ...]:
    """Axis names a collective equation operates over, from whichever
    param spelling the primitive uses (``axes``, ``axis_name``)."""
    for key in ("axes", "axis_name"):
        if key in eqn.params:
            v = eqn.params[key]
            if isinstance(v, (tuple, list)):
                return tuple(str(a) for a in v)
            return (str(v),)
    return ()


@dataclasses.dataclass(frozen=True)
class ControlFrame:
    """One enclosing control-flow equation on a collective's path."""

    kind: str  # cond | while | scan
    rank_dependent: bool  # predicate/operands tainted by axis_index


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    kind: str
    axes: Tuple[str, ...]
    order: int  # global preorder position across the whole walk
    path: str  # nesting path, e.g. "shard_map/while/psum[#12]"
    in_avals: Tuple[Any, ...]
    out_avals: Tuple[Any, ...]
    control_flow: Tuple[ControlFrame, ...]

    @property
    def in_bytes(self) -> int:
        return sum(aval_nbytes(a) for a in self.in_avals)

    @property
    def out_bytes(self) -> int:
        return sum(aval_nbytes(a) for a in self.out_avals)

    def signature(self) -> Tuple:
        """Order-comparison key: what must match for two SPMD programs to
        co-execute this collective without deadlocking."""
        return (
            self.kind,
            self.axes,
            tuple(sorted(str(a) for a in self.in_avals)),
        )


@dataclasses.dataclass(frozen=True)
class LoopCarry:
    """One carry position of a while/scan body (precision pass input)."""

    loop_kind: str  # while | scan
    position: int  # index within the carry block
    aval: Any
    path: str
    # True when the carry's ONLY use in the body is the add producing its
    # next value — a pure accumulator (c = c + x), the gradient/loss
    # accumulation shape. Residual streams (h = h + f(h)) read the carry
    # elsewhere too and are excluded.
    is_pure_add_accumulator: bool = False


@dataclasses.dataclass
class WalkResult:
    collectives: List[CollectiveSite]
    loop_carries: List[LoopCarry]
    # var -> producing (order, eqn-path) for the OUTERMOST jaxpr only;
    # used by the donation pass (it needs producer/consumer ordering at
    # one nesting level, not globally).
    n_eqns: int = 0


def _tainted(var, taint: Set[int]) -> bool:
    return not isinstance(var, Literal) and id(var) in taint


def _sub_jaxprs_generic(eqn) -> List[Any]:
    """Every Jaxpr/ClosedJaxpr reachable from the eqn's params."""
    subs = []
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if isinstance(item, ClosedJaxpr):
                subs.append(item.jaxpr)
            elif isinstance(item, Jaxpr):
                subs.append(item)
    return subs


def _map_taint_positional(
    sub, eqn_invars, taint: Set[int], offset: int = 0
) -> Set[int]:
    """Seed a sub-jaxpr's taint set from the eqn operands, aligning
    ``eqn_invars[offset:]`` with the sub-jaxpr's invars (suffix-aligned
    when lengths differ — operands map to the trailing invars for the
    call-like primitives that prepend consts)."""
    sub_taint: Set[int] = set()
    ops = list(eqn_invars[offset:])
    invars = list(sub.invars)
    if len(ops) != len(invars):
        # Align tails: extra leading invars are consts (never operands),
        # extra leading operands are consts consumed before the mapping.
        n = min(len(ops), len(invars))
        ops, invars = ops[len(ops) - n :], invars[len(invars) - n :]
    for op, iv in zip(ops, invars):
        if _tainted(op, taint):
            sub_taint.add(id(iv))
    return sub_taint


class JaxprWalker:
    """Single-pass recursive analyzer (see module docstring)."""

    def __init__(self) -> None:
        self._order = 0
        self.result = WalkResult(collectives=[], loop_carries=[])

    def walk(self, jaxpr, taint: Optional[Set[int]] = None) -> WalkResult:
        self._walk(jaxpr, taint or set(), path=(), cf=())
        return self.result

    # -- internals -------------------------------------------------------

    def _walk(
        self,
        jaxpr,
        taint: Set[int],
        path: Tuple[str, ...],
        cf: Tuple[ControlFrame, ...],
    ) -> None:
        for eqn in jaxpr.eqns:
            self._order += 1
            self.result.n_eqns += 1
            name = eqn.primitive.name
            tainted_in = any(_tainted(v, taint) for v in eqn.invars)

            if name in COLLECTIVE_PRIMS:
                self.result.collectives.append(
                    CollectiveSite(
                        kind=name,
                        axes=_axis_names(eqn),
                        order=self._order,
                        path="/".join(path + (f"{name}[#{self._order}]",)),
                        in_avals=tuple(
                            v.aval
                            for v in eqn.invars
                            if hasattr(v, "aval")
                        ),
                        out_avals=tuple(v.aval for v in eqn.outvars),
                        control_flow=cf,
                    )
                )

            if name == "cond":
                self._walk_cond(eqn, taint, path, cf)
            elif name == "while":
                self._walk_while(eqn, taint, path, cf)
            elif name == "scan":
                self._walk_scan(eqn, taint, path, cf)
            else:
                for sub in _sub_jaxprs_generic(eqn):
                    sub_taint = _map_taint_positional(sub, eqn.invars, taint)
                    self._walk(sub, sub_taint, path + (name,), cf)

            # Taint propagation: axis_index introduces rank dependence;
            # any eqn consuming a tainted value produces tainted outputs.
            if name == "axis_index" or tainted_in:
                for ov in eqn.outvars:
                    taint.add(id(ov))

    def _walk_cond(self, eqn, taint, path, cf) -> None:
        rank_dep = _tainted(eqn.invars[0], taint)
        frame = ControlFrame("cond", rank_dep)
        for branch in eqn.params["branches"]:
            sub = branch.jaxpr
            sub_taint = _map_taint_positional(sub, eqn.invars, taint, offset=1)
            self._walk(sub, sub_taint, path + ("cond",), cf + (frame,))

    def _walk_while(self, eqn, taint, path, cf) -> None:
        cond_n = eqn.params["cond_nconsts"]
        body_n = eqn.params["body_nconsts"]
        cond_j = eqn.params["cond_jaxpr"].jaxpr
        body_j = eqn.params["body_jaxpr"].jaxpr
        cond_consts = eqn.invars[:cond_n]
        body_consts = eqn.invars[cond_n : cond_n + body_n]
        carry = eqn.invars[cond_n + body_n :]
        # Trip count is decided by cond_jaxpr over (cond_consts, carry):
        # taint in either makes the loop rank-dependent.
        rank_dep = any(_tainted(v, taint) for v in cond_consts) or any(
            _tainted(v, taint) for v in carry
        )
        frame = ControlFrame("while", rank_dep)
        self._collect_carries(body_j, n_consts=body_n, kind="while", path=path)
        cond_taint = _map_taint_positional(
            cond_j, list(cond_consts) + list(carry), taint
        )
        body_taint = _map_taint_positional(
            body_j, list(body_consts) + list(carry), taint
        )
        self._walk(cond_j, cond_taint, path + ("while.cond",), cf + (frame,))
        self._walk(body_j, body_taint, path + ("while",), cf + (frame,))

    def _walk_scan(self, eqn, taint, path, cf) -> None:
        sub = eqn.params["jaxpr"].jaxpr
        num_consts = eqn.params["num_consts"]
        # scan's trip count is static — never rank-dependent — but a
        # collective inside still executes once per iteration.
        frame = ControlFrame("scan", False)
        self._collect_carries(
            sub,
            n_consts=num_consts,
            kind="scan",
            path=path,
            n_carry=eqn.params["num_carry"],
        )
        sub_taint = _map_taint_positional(sub, eqn.invars, taint)
        self._walk(sub, sub_taint, path + ("scan",), cf + (frame,))

    def _collect_carries(
        self, body, n_consts: int, kind: str, path, n_carry: Optional[int] = None
    ) -> None:
        carry_in = body.invars[n_consts:]
        if n_carry is not None:
            carry_in = carry_in[:n_carry]
        carry_out = body.outvars[: len(carry_in)]
        # Use counts of each body var (for the pure-accumulator test).
        uses: Dict[int, int] = {}
        producers: Dict[int, Any] = {}
        for eqn in body.eqns:
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    uses[id(v)] = uses.get(id(v), 0) + 1
            for ov in eqn.outvars:
                producers[id(ov)] = eqn
        for pos, (civ, cov) in enumerate(zip(carry_in, carry_out)):
            pure_acc = False
            prod = producers.get(id(cov))
            if (
                prod is not None
                and prod.primitive.name in ("add", "add_any")
                and any(
                    not isinstance(v, Literal) and v is civ
                    for v in prod.invars
                )
                and uses.get(id(civ), 0) == 1
            ):
                pure_acc = True
            self.result.loop_carries.append(
                LoopCarry(
                    loop_kind=kind,
                    position=pos,
                    aval=getattr(civ, "aval", None),
                    path="/".join(tuple(path) + (kind,)),
                    is_pure_add_accumulator=pure_acc,
                )
            )


def collect(closed_jaxpr) -> WalkResult:
    """Walk a ClosedJaxpr (or Jaxpr) and return the analysis inputs."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return JaxprWalker().walk(jaxpr)
