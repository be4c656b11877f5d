"""Trace-time SPMD linter: static collective/donation/precision analysis.

The reference's correctness machinery is *runtime* — StallInspector
timeouts, Timeline forensics, negotiation mismatch aborts — so a
mismatched collective or rank-divergent control flow only surfaces as a
hang on real hardware. Here the whole train step is one traced SPMD
program, so every one of those invariants is checkable **statically**
from the jaxpr, on CPU, before a single device-second is spent:

* :func:`lint_traced` — trace any step function with ``jax.make_jaxpr``
  (no devices execute) and run the four rule families over it:
  collective consistency, fusion parity, donation, precision (rule
  catalog: :mod:`.findings`).
* :func:`trace_collectives` — just the walk (collective sites + loop
  carries), for custom checks.
* :func:`compare_collectives` / :func:`static_parity` — cross-build
  checks: co-executable builds must emit identical collective sequences;
  the sharded (ZeRO-1) build must hold byte parity with the replicated
  one (the static twin of ``tools/comm_audit.py --parity``).

* :func:`schedule_cert` / :class:`~.certify.ScheduleCert` — whole-
  program certification (:mod:`.certify`): a canonical fingerprint of
  the collective schedule, the cross-rank preflight gate
  (:func:`publish_and_verify`, armed by ``HVDTPU_CERT``) and the
  first-divergence diagnosis (:func:`diff_certs`). CLI:
  ``tools/hvdtpu_verify.py``.

* :func:`plan_traced` / :class:`~.memory.MemoryPlan` — the static HBM
  planner (:mod:`.memory`): linear-scan buffer lifetimes over the same
  traced jaxpr, extending this plane from *wire bytes* to *resident
  bytes* (peak per-device HBM, donation/remat/sharding deltas, the
  ``oom-risk``/``donation-missed-reuse``/``peak-regression`` rules).

* :func:`collective_schedule` (:mod:`.schedule`) — the one reading of a
  COMPILED step here: from ``compiled.as_text()``, the synchronous and
  asynchronous gradient collectives, their bytes, and what the schedule
  puts between each pair's start and done (how far the overlapped
  exchange engaged; ``tools/comm_audit.py --schedule``).

Entry points that wrap this for daily use: ``parallel.dp.make_train_step
(lint=...)`` (every built step can self-lint, and exposes
``step.memplan()``), ``tools/hvdtpu_lint.py`` / ``tools/
hvdtpu_memplan.py`` (CLIs over the bundled model zoo),
``tools/comm_audit.py --lint`` and ``tools/run_lints.py`` (CI umbrella).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax

from ..utils import env as _env
from .findings import (  # noqa: F401
    LintError,
    LintFinding,
    Severity,
    apply_allowlist,
    errors,
    max_severity,
)
from .jaxpr_walk import CollectiveSite, WalkResult, collect  # noqa: F401
from .certify import (  # noqa: F401
    CertMismatchError,
    KVCertChannel,
    ScheduleCert,
    diff_certs,
    publish_and_verify,
    schedule_cert,
    schedule_entries,
)
from .memory import (  # noqa: F401
    MemoryLintConfig,
    MemoryPlan,
    plan_traced,
)
from .schedule import collective_schedule  # noqa: F401
from . import rules as _rules


def _leaf_labels(args: Tuple) -> list:
    """Human labels for the flattened leaves of ``args`` (matching
    ``jax.make_jaxpr``'s invar order): ``arg0.params['w']`` style."""
    labels = []
    for i, arg in enumerate(args):
        paths = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, _ in paths:
            labels.append(f"arg{i}" + jax.tree_util.keystr(path))
    return labels


def _donated_mask(args: Tuple, donate_argnums: Sequence[int]) -> list:
    donate = frozenset(donate_argnums)
    mask = []
    for i, arg in enumerate(args):
        n = len(jax.tree_util.tree_leaves(arg))
        mask.extend([i in donate] * n)
    return mask


def publish_peak_bytes(plan) -> None:
    """ONE home for the ``memplan.peak_bytes`` gauge (metric-name lint:
    a name has exactly one owning module). Both surfaces that compute a
    plan — ``step.memplan()`` and the armed-lint path — publish through
    here, so hvdtpu_top's "hbm plan" column fills on either recipe."""
    from ..obs import registry as _obs

    _obs.metrics().gauge("memplan.peak_bytes").set(plan.peak_bytes)


def trace_collectives(fn, args: Tuple) -> WalkResult:
    """Trace ``fn(*args)`` abstractly and walk the jaxpr. ``args`` may be
    arbitrary pytrees of arrays or ``ShapeDtypeStruct`` leaves — nothing
    executes and no devices are needed."""
    return collect(jax.make_jaxpr(fn)(*args))


def lint_traced(
    fn,
    args: Tuple,
    *,
    donate_argnums: Sequence[int] = (),
    declared_axes=None,
    params=None,
    sharded: bool = False,
    threshold_bytes: Optional[int] = None,
    world: Optional[int] = None,
    allow_low_precision_collectives: bool = False,
    allowlist: Sequence[str] = (),
    jaxpr=None,
    quant=None,
    compute_dtype: str = "",
    act_quant: str = "",
    wire_dtype=None,
    gather_wire_dtype=None,
    memory: Optional[MemoryLintConfig] = None,
) -> Tuple[LintFinding, ...]:
    """Run every applicable lint pass over a traced step.

    Args:
      fn: the step function **before** ``jax.jit`` (typically the
        ``shard_map``-wrapped body, so collective axes are bound).
      args: example arguments (abstract ``ShapeDtypeStruct`` pytrees are
        fine — tracing never executes).
      donate_argnums: positions in ``args`` whose buffers the jitted step
        donates; enables the donation passes.
      declared_axes: axis names collectives may legally use (defaults to
        skipping the axis check when None).
      params: the parameter/gradient tree (abstract ok). When given with
        ``world``, the fusion-parity pass checks that the fusion policy's
        predicted buckets appear as collective groups.
      sharded: the step uses the ZeRO-1 reduce-scatter/all-gather update
        (changes which collective kinds fusion parity matches, and the
        padding the prediction applies).
      threshold_bytes: fusion threshold (default: env knob).
      world: data-parallel world size (bucket padding for sharded parity).
      allow_low_precision_collectives: suppress the bf16/fp16 reduction
        rule — set when wire compression was explicitly requested.
      allowlist: rule suppressions (see :mod:`.findings`).
      jaxpr: a pre-traced ClosedJaxpr of ``fn(*args)`` — pass it when
        the caller already traced (avoids re-tracing large models).
      quant: the quantized compressor the step was built with
        (``Compression.int8``-style), or None. Switches fusion parity to
        the quantized-wire prediction: each bucket must appear as one
        all-to-all and one all-gather group in the wire dtype, padded to
        ``world * block`` (see ``ops/fusion.quantized_bucket_layout``).
      compute_dtype / act_quant: the low-precision compute modes the
        step was built with (``make_train_step(compute_dtype=,
        act_quant=)``) — feed the :func:`~.rules.rule_low_precision`
        pass: fp8 dots whose scale state is missing from ``params`` are
        ERRORs (``low-precision-unverified``); an act-quant request the
        model never consumed is a WARNING (``act-quant-unconsumed``).
        The fp8 check runs unconditionally (a hand-rolled fp8 cast is
        broken whether or not the knob was declared).
      wire_dtype: cast-compressor wire dtype (fp16/bf16) — fusion parity
        then predicts bucket bytes in the wire dtype, matching what the
        compressed collectives actually emit.
      memory: a :class:`MemoryLintConfig` arms the static HBM pass
        (:mod:`.memory`): the step is planned from the SAME traced
        jaxpr (no re-trace) and the ``oom-risk`` /
        ``donation-missed-reuse`` / ``peak-regression`` rules run over
        the plan. ``None`` (default) skips it.

    Returns the findings that survive the allowlist, most severe first.
    """
    if threshold_bytes is None:
        threshold_bytes = _env.fusion_threshold_bytes()
    closed = jaxpr if jaxpr is not None else jax.make_jaxpr(fn)(*args)
    walk = collect(closed)

    findings: list = []
    findings += _rules.rule_axis_names(walk.collectives, declared_axes)
    findings += _rules.rule_control_flow(walk.collectives)
    findings += _rules.rule_rs_ag_pairing(walk.collectives)
    findings += _rules.rule_precision_collectives(
        walk.collectives,
        allow_low_precision=allow_low_precision_collectives,
    )
    findings += _rules.rule_precision_accumulators(walk)
    findings += _rules.rule_low_precision(
        closed, params, compute_dtype=compute_dtype, act_quant=act_quant
    )
    if params is not None and world:
        findings += _rules.rule_fusion_parity(
            walk.collectives,
            params,
            threshold_bytes=threshold_bytes,
            world=world,
            sharded=sharded,
            quant=quant,
            wire_dtype=wire_dtype,
            gather_wire_dtype=gather_wire_dtype,
        )
    if donate_argnums:
        findings += _rules.rule_donation(
            closed,
            _donated_mask(args, donate_argnums),
            _leaf_labels(args),
        )
    if memory is not None:
        plan = plan_traced(
            fn,
            args,
            donate_argnums=donate_argnums,
            world=world or 1,
            jaxpr=closed,
        )
        publish_peak_bytes(plan)
        findings += _rules.rule_memory(
            plan,
            budget_bytes=memory.budget_bytes,
            baseline_bytes=memory.baseline_bytes,
            baseline_key=memory.baseline_key,
            donation_threshold=memory.donation_threshold,
            regression_tolerance=memory.regression_tolerance,
        )
    kept = apply_allowlist(findings, allowlist)
    return tuple(sorted(kept, key=lambda f: -int(f.severity)))


def compare_collectives(
    fn_a,
    args_a: Tuple,
    fn_b,
    args_b: Tuple,
    *,
    label_a: str = "build A",
    label_b: str = "build B",
) -> Tuple[LintFinding, ...]:
    """Static deadlock check between two builds that must co-execute
    (e.g. the same step at ``accum_steps=1`` vs ``K`` during a rolling
    reconfiguration): identical collective count, order and signatures."""
    wa = trace_collectives(fn_a, args_a)
    wb = trace_collectives(fn_b, args_b)
    return _rules.rule_order_divergence(
        wa.collectives, wb.collectives, label_a=label_a, label_b=label_b
    )


def static_parity(
    fn_replicated,
    args_replicated: Tuple,
    fn_sharded,
    args_sharded: Tuple,
    *,
    params,
    world: int,
    threshold_bytes: Optional[int] = None,
    tolerance: float = 1.1,
) -> Tuple[LintFinding, ...]:
    """Replicated-vs-sharded byte parity from jaxprs alone — the static
    twin of ``tools/comm_audit.py --parity`` (no subprocesses, no
    compile). Returns findings on bucket-count or ring-wire divergence."""
    if threshold_bytes is None:
        threshold_bytes = _env.fusion_threshold_bytes()
    rep = trace_collectives(fn_replicated, args_replicated)
    shard = trace_collectives(fn_sharded, args_sharded)
    return _rules.rule_wire_parity(
        rep.collectives,
        shard.collectives,
        params,
        threshold_bytes=threshold_bytes,
        world=world,
        tolerance=tolerance,
    )


def ring_wire_bytes(sites: Sequence[CollectiveSite], world: int) -> int:
    """Re-export of the ring accounting shared with ``comm_audit``."""
    return _rules.ring_wire_bytes(sites, world)
