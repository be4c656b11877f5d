"""Data-parallel training step builder.

The end-to-end shape of the reference's training recipe (wrap optimizer →
broadcast initial state → every step allreduces gradients;
``README.rst:60-61``, ``horovod/torch/optimizer.py``) compiled into a
single SPMD program: per-device forward/backward on the local batch shard,
one fused psum per gradient bucket, identical optimizer update everywhere.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
import warnings
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..context import _axis_or_world, context as _get_context
from ..obs import registry as _obs
from ..optimizer import (
    DistributedOptimizer,
    ShardedDistributedOptimizer,
    ef_residual_norm,
    guarded_commit,
    sharded_state_specs,
)
from ..ops.collectives import Average, ReduceOp, allreduce
from ..ops.compression import Compression, is_quantized
from ..ops.layout import (
    collective_compiler_options,
    overlap_compiler_options,
    overlap_threshold_bytes,
)
from ..utils import env as _env


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray
    extra: Any = None  # e.g. flax batch_stats
    # Fail-silent defense bookkeeping (guard.GuardState of replicated
    # scalars) when the step was built with guard=...; None otherwise —
    # and None flattens to an empty subtree, so unguarded states keep
    # their historical pytree structure (checkpoints, specs, caches).
    guard: Any = None

    def tree_flatten(self):
        return (
            self.params, self.opt_state, self.step, self.extra, self.guard
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def accumulate_gradients(
    loss_fn: Callable,
    params,
    batch,
    accum_steps: int,
    *,
    has_aux: bool = False,
) -> Tuple[Any, Any, Any]:
    """Microbatched ``value_and_grad`` with local, collective-free
    accumulation — the compute half of the overlap pipeline.

    Every batch leaf is split along dim 0 into ``accum_steps`` equal
    microbatches. The first ``accum_steps - 1`` run inside a rolled
    ``lax.fori_loop`` (compile time independent of K) accumulating
    gradients locally; the **last microbatch is peeled out of the loop**,
    so its backward pass and whatever the caller does with the returned
    gradients (the fused per-bucket collectives, in
    :func:`make_train_step`) live in one flat dataflow region: bucket
    ``b``'s collective depends only on bucket ``b``'s leaves of this
    final backward, and the scheduler can issue the first-ready buckets
    while the tail of the backward still computes. The collectives
    themselves are NOT inside the accumulation loop — one reduction per
    step regardless of K, so wire bytes are identical to the
    unmicrobatched step (checked by ``tools/comm_audit.py
    --microbatch-parity``).

    Mean semantics: returns the mean of the per-microbatch losses and the
    mean of the per-microbatch gradients — exactly the full-batch mean
    when ``loss_fn`` itself is a per-batch mean (the standard shape; a
    sum-style loss would come back divided by ``accum_steps``). Loss AND
    gradients are accumulated in fp32 (the mean gradient is returned in
    the gradient's own dtype), so low-precision params don't round the
    running sum K-1 times. ``aux`` (with ``has_aux``) is the LAST
    microbatch's aux — auxiliaries like batch stats see 1/K of the batch.

    Returns ``(loss, aux, grads)``; ``aux`` is None without ``has_aux``.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def one(p, mb):
        out, g = jax.value_and_grad(loss_fn, has_aux=has_aux)(p, mb)
        loss, aux = out if has_aux else (out, None)
        return loss, aux, g

    if accum_steps == 1:
        return one(params, batch)

    for leaf in jax.tree.leaves(batch):
        if leaf.shape[0] % accum_steps:
            raise ValueError(
                f"batch dim {leaf.shape[0]} not divisible by "
                f"accum_steps={accum_steps} (every batch leaf's leading "
                "dim must split into equal microbatches)"
            )

    def micro(i):
        # i may be traced (fori_loop index); per-leaf microbatch size is
        # static so this lowers to one dynamic-slice per leaf.
        return jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(
                x, i * (x.shape[0] // accum_steps), x.shape[0] // accum_steps
            ),
            batch,
        )

    # Accumulate in fp32 like the loss: K-1 low-precision adds would
    # round the running sum every microbatch and break the parity
    # contract for bf16/fp16 params. The mean is cast back to the
    # gradient's own dtype (a no-op for fp32 params).
    def body(i, carry):
        acc, loss_sum = carry
        loss_i, _, g_i = one(params, micro(i))
        return (
            jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, g_i),
            loss_sum + loss_i.astype(jnp.float32),
        )

    zero_g = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    acc, loss_sum = jax.lax.fori_loop(
        0, accum_steps - 1, body, (zero_g, jnp.zeros((), jnp.float32))
    )
    loss_k, aux, g_k = one(
        params, jax.tree.map(lambda x: x[-(x.shape[0] // accum_steps):], batch)
    )
    grads = jax.tree.map(
        lambda a, g: (
            (a + g.astype(jnp.float32)) / accum_steps
        ).astype(g.dtype),
        acc,
        g_k,
    )
    loss = (loss_sum + loss_k.astype(jnp.float32)) / accum_steps
    return loss, aux, grads


def _instrument_step(fn: Callable, tokens_per_step, flops_per_step,
                     overlap: bool = False, accum_steps: int = 1,
                     quantized: bool = False, fp8: bool = False) -> Callable:
    """Outermost wrapper of a built train step: the ``hvd.step.dispatch``
    span always, the metrics / trace / goodput bookkeeping when a plane
    is on.

    The enablement check is per *call*, not per build, so the documented
    ``hvd.obs.enable()``/``disable()`` work on an already-built step:
    with every plane off a call pays one cached-boolean check per plane
    and the span (a profiler annotation, inert without a session).

    With a plane on the wrapper never waits for the step it has just
    dispatched. It dispatches step i, then blocks on step i-1's *loss*
    (``hvd.step.sync``; never on the state, never on step i's output)
    and stamps the clock: the loop of a user who logs the loss one step
    late, so the device always holds a queued step and turning a plane
    on does not change what it measures. Each stamp books the step it
    closes: ``step.total_ms`` is the gap since the previous stamp (or
    since this step's dispatch began, where that is later: the first
    step, or a loop that paused), ``step.host_dispatch_ms`` the time its
    ``step(state, batch)`` call took to return, ``step.device_ms`` the
    rest of the gap. N calls book N-1 steps; the last one stays pending.
    The ring's ``step`` / ``step.host_dispatch`` / ``step.device``
    events get the same three quantities, ``goodput.record_step`` the
    first two (the third is their difference);
    the reporter is ticked with the call count so JSONL/Prometheus
    flushes and the psum'd rank-0 summary ride the training loop with no
    extra threads.
    """
    from ..obs import build as _build
    from ..obs import export as _export
    from ..obs import flops as _flops
    from ..obs import goodput as _goodput
    from ..obs import trace as _trace

    peak = None  # resolved once, first instrumented step
    # The cross-process summary in tick() must fire on the same call on
    # every rank. The registry's step.count counter is process-cumulative
    # and diverges after an elastic rescale (a fresh worker starts at 0
    # while survivors carry their history), which would leave ranks
    # entering the blocking summary allreduce on different iterations —
    # so the collective is keyed to this wrapper-local call counter
    # instead, reset to zero on every (re)build, which rescales perform
    # on all ranks in lockstep.
    local_step = 0
    # The dispatched step no stamp has closed yet, (loss, perf_counter
    # and wall clock at its dispatch, dispatch seconds, call number), and
    # the perf_counter of the last stamp.
    pending = None
    last_stamp = None

    def book(step, t_done):
        """A stamp closed ``step``: histograms, gauges, ring, ledger."""
        nonlocal peak, last_stamp
        _, t0, w0, dispatch, n = step
        begin = t0 if last_stamp is None else max(t0, last_stamp)
        last_stamp = t_done
        total = t_done - begin
        device = max(0.0, total - dispatch)
        w_begin = w0 + (begin - t0)
        if _trace.enabled():
            # The step and its two bookkeeping slices as nested X events
            # (wall-clock ts so the merge tool can align ranks). Where
            # the host really was is in the hvd.step.* spans.
            rec = _trace.recorder()
            w_us, disp_us = int(w_begin * 1e6), int(dispatch * 1e6)
            rec.complete(
                "step", "train", w_us, int(total * 1e6), args={"step": n}
            )
            rec.complete("step.host_dispatch", "train", w_us, disp_us)
            rec.complete(
                "step.device", "train", w_us + disp_us, int(device * 1e6)
            )
        _goodput.record_step(w_begin, total, dispatch)
        reg = _obs.metrics()
        reg.histogram("step.total_ms").observe(total * 1e3)
        reg.histogram("step.host_dispatch_ms").observe(dispatch * 1e3)
        reg.histogram("step.device_ms").observe(device * 1e3)
        if total <= 0:
            return
        reg.gauge("step.per_sec").set(1.0 / total)
        if tokens_per_step:
            reg.gauge("step.tokens_per_sec").set(tokens_per_step / total)
        if flops_per_step:
            if peak is None:
                peak = _flops.peak_tflops(jax.devices()[0])
            # mfu() treats its first two args as (units/sec, flops/unit);
            # with one step as the unit that's steps/sec × flops/step.
            m = _flops.mfu(1.0 / total, flops_per_step, peak=peak)
            if m is not None:
                reg.gauge("step.mfu").set(m)

    def wrapped(state, batch):
        nonlocal local_step, pending, last_stamp
        n = local_step
        local_step = n + 1
        # obs/build.py books a build that happens inside this call
        # against the call's number.
        _build.in_step.call = n
        try:
            if not (_obs.enabled() or _trace.enabled()
                    or _goodput.enabled()):
                pending = last_stamp = None
                with _trace.span("hvd.step.dispatch", "train", step=n):
                    return fn(state, batch)
            w0 = time.time()
            t0 = time.perf_counter()
            with _trace.span("hvd.step.dispatch", "train", step=n):
                out = fn(state, batch)
            dispatch = time.perf_counter() - t0
        finally:
            _build.in_step.call = None
        closed, pending = pending, (out[1], t0, w0, dispatch, n)
        if closed is not None:
            prev_loss, *_, prev_n = closed
            with _trace.span("hvd.step.sync", "train", step=prev_n):
                jax.block_until_ready(prev_loss)
            book(closed, time.perf_counter())
        reg = _obs.metrics()
        reg.counter("step.count").inc()
        # Overlap-pipeline shape of this step (how hvdtpu_top and a reader
        # of the exported records tell an overlap=True build from the default).
        reg.gauge("overlap.enabled").set(1.0 if overlap else 0.0)
        reg.gauge("overlap.accum_steps").set(accum_steps)
        if tokens_per_step:
            reg.counter("step.tokens").inc(int(tokens_per_step))
        if quantized and _obs.enabled() and local_step % 10 == 1:
            # First step, then every 10. Live EF health: a residual norm
            # that grows without bound means the quantizer is dropping
            # more than the next step re-feeds (block too large for the
            # gradient's dynamic range). This is an eager reduction over
            # the GLOBAL residual state (world x gradient-sized fp32),
            # so it is sampled every 10th step rather than paid on each
            # one (it waits for this step, the one block the lagged
            # stamps leave) — and METRICS-plane-only (a trace-only run
            # must not pay a real reduction for a gauge the null
            # registry drops).
            norm = ef_residual_norm(out[0].opt_state)
            if norm is not None:
                reg.gauge("quant.residual_norm").set(norm)
        if fp8 and _obs.enabled() and local_step % 10 == 1:
            # fp8 delayed-scaling health, sampled like the EF norm above
            # (eager reductions over every amax ring / cast residual).
            # A runaway amax_max or collapsing scale_min is the leading
            # indicator the runbook's fp8-divergence ladder keys off.
            from ..ops.fp8 import fp8_state_gauges

            g = fp8_state_gauges(out[0].params)
            if g:
                reg.gauge("fp8.amax_max").set(g["fp8.amax_max"])
                reg.gauge("fp8.scale_min").set(g["fp8.scale_min"])
                reg.gauge("fp8.cast_residual_norm").set(
                    g["fp8.cast_residual_norm"]
                )
        _export.reporter().tick(step=local_step)
        return out

    return wrapped


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    has_aux: bool = False,
    distribute_optimizer: bool = True,
    op: ReduceOp = Average,
    compression=None,
    axis=None,
    donate: bool = True,
    mesh=None,
    batch_spec=None,
    sharded: bool = False,
    gather_compression=Compression.none,
    threshold_bytes: Optional[int] = None,
    tokens_per_step: Optional[int] = None,
    flops_per_step: Optional[float] = None,
    overlap: Optional[bool] = None,
    accum_steps: Optional[int] = None,
    stagger: Optional[bool] = None,
    lint: Optional[Union[bool, str]] = None,
    lint_allow: Sequence[str] = (),
    error_feedback: bool = True,
    guard: Optional[Union[bool, Any]] = None,
    fused_update: Optional[bool] = None,
    remat: Optional[Union[bool, str, Callable]] = None,
    compute_dtype: Optional[str] = None,
    act_quant: Optional[str] = None,
    autotune: Optional[Union[bool, Any]] = None,
    publish: Optional[int] = None,
) -> Tuple[Callable, optax.GradientTransformation]:
    """Build a jitted SPMD train step.

    ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)`` with
    ``has_aux=True``) is evaluated on each device's batch shard; gradients
    are averaged across the world by wrapping ``optimizer`` in
    :func:`DistributedOptimizer` (pass ``distribute_optimizer=False`` if it
    already is distributed).

    ``sharded=True`` selects the ZeRO-1 sharded weight update
    (:func:`ShardedDistributedOptimizer`): optimizer state lives dim-0
    sharded over the world axis (1/N per replica), the update runs on the
    local shard between a reduce-scatter and an all-gather, and the train
    step's in/out specs carry the sharding so ``TrainState`` donation
    keeps working. ``gather_compression`` compresses the all-gather leg.

    Returns ``(step_fn, wrapped_optimizer)``; use the wrapped optimizer's
    ``init`` for the initial state (:func:`init_state` does this).
    ``step_fn(state, batch) -> (state, loss[, aux])``; the loss is the
    world average.

    With ``HVDTPU_METRICS=1`` the returned step is wrapped with the
    telemetry bracket (:mod:`horovod_tpu.obs`): per-step host-dispatch /
    device breakdown, step counters, and — when the caller supplies the
    model shape — throughput and MFU. ``tokens_per_step`` is the global
    tokens (or samples) one step consumes; ``flops_per_step`` the
    analytic training FLOPs per step *per chip*
    (:mod:`horovod_tpu.obs.flops` has the shared model). Both are
    ignored, costing nothing, when metrics are off.

    **Overlapped gradient exchange** (the default, since PR 29): when
    the reduction axis spans more than one device, the replicated step
    (``sharded=False``, no wire quantization) is compiled with the
    options that lower every single-operand gradient all-reduce to an
    asynchronous start/done pair
    (:func:`~horovod_tpu.ops.layout.overlap_compiler_options`) and with
    the combiner held to
    :func:`~horovod_tpu.ops.layout.overlap_threshold_bytes`, so that
    every gradient leaf of 1 MiB or more keeps a reduction of its own:
    the compiled schedule puts weight-gradient matmuls and optimizer
    updates of other parameters between start and done. Nothing to
    switch on, no new argument; the same trace, the same float32 sums
    over the same devices. The step also places a state that is not on
    the mesh (``init_state`` leaves it on the default device) before it
    dispatches it, and ``step.lower`` lowers for that place, so the second
    call builds nothing. With one device on the axis the step is built exactly as
    before: no compiler option, the same compiled program.
    :func:`horovod_tpu.analysis.collective_schedule` reads how far the
    overlap engaged off ``step.lower(...).compile().as_text()``.

    **Overlap pipeline** (opt-in; defaults read the ``HVDTPU_OVERLAP*``
    knobs): ``accum_steps=K`` microbatches the step through
    :func:`accumulate_gradients` — K forward/backward passes over 1/K
    batch slices, gradients accumulated locally, ONE fused reduction of
    the mean gradient per step (wire bytes identical to ``accum_steps=1``).
    ``overlap=True`` adds per-bucket staggered dispatch in readiness
    order (reverse-layer packing + ``optimization_barrier`` chaining,
    see ``ops/fusion.py``; ``stagger=False`` lets the scheduler
    free-order buckets, an explicit ``stagger=True`` chains them even
    without ``overlap`` — default reads ``HVDTPU_OVERLAP_STAGGER``) and
    passes the same compile options on the ``sharded=True`` and
    quantized paths too. Per-compile options only: the step's path never
    writes ``XLA_FLAGS``
    (:func:`~horovod_tpu.context.enable_overlap_scheduler` is for callers
    who set the environment before ``hvd.init()``). Both knobs
    work on the replicated and ``sharded=True`` paths, preserve donation,
    and are numerically the plain step within fp tolerance (the
    accumulation reorders the sum; ``tests/test_overlap.py``). On CPU
    test platforms the scheduler options degrade to no-ops.

    **Quantized collectives**: ``compression=Compression.int8`` /
    ``Compression.fp8`` (default from ``HVDTPU_QUANT``) puts the
    gradient reduction on a blockwise-quantized wire — ~0.51x the bf16
    cast's ring bytes at the default ``HVDTPU_QUANT_BLOCK=256`` — on
    BOTH the replicated and ``sharded=True`` paths (the sharded update
    all-gather rides the same wire unless ``gather_compression`` says
    otherwise). Error feedback is on by default: per-bucket fp32
    residuals join the optimizer state (dim-0 sharded over the world
    axis like the ZeRO-1 buckets, donated, checkpointed canonically,
    resharded on elastic rescale); ``error_feedback=False`` drops them.
    See ``docs/api.md`` "Quantized collectives" for the wire format, EF
    semantics and when NOT to quantize.

    **Static lint** (:mod:`horovod_tpu.analysis`): the returned step
    always exposes ``step.lint(state, batch) -> findings`` — trace the
    exact program this builder assembled (no devices execute) and run
    the SPMD rule passes: collective consistency, fusion parity against
    the ``PackSpec`` policy, donation liveness, precision. ``lint=``
    arms it automatically on the FIRST call: ``"warn"`` emits a Python
    warning per finding, ``"raise"`` raises
    :class:`~horovod_tpu.analysis.LintError` on ERROR-severity findings
    before any compute is dispatched (``True`` means ``"warn"``;
    default reads ``HVDTPU_LINT``). ``lint_allow`` suppresses rules by
    id (``"rule"`` or ``"rule:provenance-substring"``); an explicit
    wire ``compression`` auto-allows the low-precision-collective rule.

    **Static certification** (:mod:`horovod_tpu.analysis.certify`): the
    step also exposes ``step.certify(state, batch) -> ScheduleCert``
    (the canonical fingerprint of its collective schedule + wire
    layout) and ``step.preflight(state, batch)``. Under an elastic
    launcher the preflight arms itself on the FIRST call (default
    ``HVDTPU_CERT=warn``): the cert is published to the KV plane and
    verified all-equal across the round's hosts *before dispatching*,
    so ranks that assembled different programs fail loudly with the
    first divergent schedule index instead of hanging the pod at that
    collective. ``HVDTPU_CERT=raise`` aborts with
    :class:`~horovod_tpu.analysis.CertMismatchError`; autotune retrace
    rebuilds re-certify under a tagged key. Standalone processes pay
    one env check. Diagnose with ``tools/hvdtpu_verify.py``.

    **Fused optimizer update** (``sharded=True`` only): ``fused_update=
    True`` (default from ``HVDTPU_FUSED_UPDATE``) runs the ZeRO-1 weight
    update as ONE Pallas pass per flat shard bucket — Adam moment
    update, bias correction, weight decay, ``-lr`` scale and the
    param-dtype cast fused, instead of the optax chain's
    one-HLO-per-step HBM round-trips over the shard. Requires an
    optimizer with static hyperparameters
    (:func:`horovod_tpu.fused_adamw`); state layout and checkpoints are
    identical to the unfused build
    (``tests/test_fused_update.py`` pins bit-parity on CPU).

    **Selective rematerialization**: ``remat=`` (default from
    ``HVDTPU_REMAT``) wraps the loss function in ``jax.checkpoint`` with
    the resolved policy — ``'full'`` recomputes everything,
    ``'dots_saveable'`` keeps matmul outputs resident and recomputes
    only elementwise chains (the policy that converts HBM headroom into
    batch on transformer shapes), or any custom
    ``jax.checkpoint_policies`` callable. One knob for the whole zoo —
    see :mod:`horovod_tpu.ops.remat`; per-block model-config remat
    (``TransformerConfig.remat``) accepts the same values.

    **Static memory plan** (:mod:`horovod_tpu.analysis.memory`): the
    returned step also exposes ``step.memplan(state, batch) ->
    MemoryPlan`` — the per-device HBM high-water mark of the exact
    program this builder assembled, from the traced jaxpr alone
    (params / opt state / activations / wire / workspace breakdown,
    donation savings, no devices execute). The lint surface runs the
    memory rules over the same trace: ``oom-risk`` gates against
    ``HVDTPU_HBM_BUDGET_GB`` when declared, ``donation-missed-reuse``
    flags aliasable-but-undonated buffers. ``step.trace(state, batch)``
    returns the ClosedJaxpr so sweep callers can share one trace
    between lint and memplan; ``step.lower(state, batch)`` returns the
    ``jax.stages.Lowered`` of the jitted program the step dispatches
    (its compiled HLO and memory analysis; nothing executes).

    **Low-precision compute** (:mod:`horovod_tpu.ops.fp8` /
    :mod:`horovod_tpu.ops.actquant`): ``compute_dtype='fp8'`` (default
    from ``HVDTPU_COMPUTE_DTYPE``) arms fp8 training matmuls for models
    built with the matching config (``TransformerConfig.compute_dtype``):
    e4m3 forward operands, e5m2 incoming gradients, per-tensor delayed
    scaling whose amax/scale state rides ``TrainState.params`` as
    ``fp8_*`` leaves — the base optimizer is wrapped so those leaves are
    overwritten with their gradient-carried new values instead of being
    Adam-stepped, and the gradient allreduce gives them replica-uniform
    mean-of-amax semantics (requires ``op=Average``; replicated path
    only — the ZeRO-1 flat buckets cannot mask fp8 state slices).
    ``act_quant='int8'`` (default from ``HVDTPU_ACT_QUANT``) stores the
    backward residuals at model-declared boundaries as int8 payload +
    fp32 scales via a names-based checkpoint policy composed with
    ``remat=`` — see docs/api.md "Low-precision compute" for when NOT
    to use either.

    **Fail-silent fault defense** (:mod:`horovod_tpu.guard`):
    ``guard=True`` (or a :class:`~horovod_tpu.guard.GuardConfig`;
    default reads ``HVDTPU_GUARD``) arms the in-graph gradient guard —
    a fused isfinite + global-norm screen over every step's gradients,
    made replica-uniform by two scalar psums. On a NaN/Inf storm or an
    EMA-z-score norm spike (``HVDTPU_GUARD_SPIKE_SIGMA``) the step is
    *skipped*: params, optimizer state and EF residuals pass through
    unchanged via ``lax.cond`` and ``state.step`` does not advance (a
    deterministic pipeline retries the step). Guard bookkeeping rides
    ``TrainState.guard`` (seeded automatically on first call);
    ``HVDTPU_GUARD_MAX_SKIPS`` consecutive skips escalate to a
    recoverable ``HorovodInternalError`` so the elastic restore path
    takes over, and every ``HVDTPU_GUARD_AUDIT_EVERY`` committed steps
    a cross-replica checksum audit detects, localizes (majority vote)
    and heals (broadcast-resync, or checkpoint walk-back for
    vote-unverifiable state) silent replica divergence whenever a
    multi-process native world is live. See ``docs/api.md``
    "Fail-silent fault defense" and ``docs/runbook.md``.

    **Live weight streaming** (:mod:`horovod_tpu.stream`): ``publish=N``
    (default reads ``HVDTPU_PUBLISH_EVERY``; 0 disables) attaches a
    :class:`~horovod_tpu.stream.WeightPublisher` to the step — every N
    committed steps the new params are packed into per-bucket deltas and
    published (CRC-framed, epoch-stamped) through the rendezvous KV for
    the decode fleet's :class:`~horovod_tpu.stream.StreamSubscriber`.
    With ``guard=True`` the publisher is gated on the consistency
    audit's verdict: a captured delta waits until an audit verifies a
    step at or beyond it, and captures covered by a divergence report
    are discarded. The publisher is exposed as
    ``step.stream_publisher``. See docs/api.md "Live weight streaming".

    **Closed-loop autotuning** (:mod:`horovod_tpu.tune`):
    ``autotune=True`` (or an ``AutotuneConfig``; default reads
    ``HVDTPU_AUTOTUNE``) wraps the returned step in the worker half of
    the knob search — per-step wall timing feeds warmup-discarded
    scoring windows, candidate vectors arrive through the elastic KV
    plane (lockstep switch at a published step boundary) or a local
    search when no driver exists, cheap knobs flip in place and
    retrace knobs rebuild the compiled step. The wrapper exposes the
    client as ``step.autotune`` (``.done``, ``.best``,
    ``.switch_log``). Knobs the call pins explicitly (``stagger=``,
    ``threshold_bytes=``) leave the search space; paths whose *state
    structure* depends on the bucket layout (``sharded=True``,
    quantized error feedback, ``fused_update``) pin the fusion
    threshold too — see docs/api.md "Autotuning" for when not to.
    """
    # First statement: here locals() holds the call's arguments and no more.
    args = _StepArgs(**locals())
    options = _resolve(args)
    if options.autotune is not None:
        tuned = _attach_autotuner(args, options)
        if tuned is not None:
            return tuned, tuned.opt
        # Empty effective space (every live knob pinned by this build):
        # fall through and build the plain untuned step.
    return _build(options)


# The arguments as the caller gave them (unset stays None): one frozen
# record cut from the signature itself, so that no second list of them can
# drift from it. The autotuner rebuilds from this record, not from the
# resolved options, because a rebuild must read the environment anew.
_StepArgs = dataclasses.make_dataclass(
    "_StepArgs", list(inspect.signature(make_train_step).parameters),
    frozen=True,
)


# Options as resolved: the same fields, each holding what the build uses.
# No None is left where the environment has a default; ``lint`` is a mode
# ("" | "warn" | "raise"), ``guard`` a GuardConfig or None, ``autotune`` an
# AutotuneConfig or None, ``publish`` a cadence (0: no weight stream),
# ``mesh`` and ``batch_spec`` are filled in, a quantized ``compression`` has
# its block pinned. Five more follow from them: ``quantized``, the
# context's ``world_axes``, and the compile-time half of the gradient
# exchange (ops/layout.py). Wherever the replicated step's reduction axis
# spans more than one device the compiler is told to lower all-reduces
# asynchronously and to merge no gradient leaf that passes the size rule
# with another: ``reduction_limit`` is the most bytes one reduction of this
# step holds, and ``certify``'s wire layout follows it. ``overlap=True``
# passes the same options on the other paths, at the fusion threshold.
# Per-compile options only (``copts``); {} on the CPU test platform and
# none at all on one device, so that step compiles as it always did.
_Options = dataclasses.make_dataclass(
    "_Options",
    [f.name for f in dataclasses.fields(_StepArgs)] + [
        "quantized", "world_axes", "overlapped_exchange", "reduction_limit",
        "copts",
    ],
    frozen=True,
)


def _resolve(args):
    """Arguments as given -> options as resolved: the one place where an
    argument of :func:`make_train_step` meets its ``HVDTPU_*`` twin, and
    the one place that validates. Traces nothing and builds nothing.

    (``HVDTPU_CERT`` is read on the step's first call and
    ``HVDTPU_HBM_BUDGET_GB`` on each ``step.lint``: when they are read is
    behaviour. ``fused_update`` and ``threshold_bytes`` meet their twins
    in ``optimizer.py``.)"""
    autotune = None
    if args.autotune is not False:
        from .. import tune as _tune

        autotune = _tune.resolve(args.autotune)
    ctx = _get_context()
    compression = args.compression
    if compression is None:
        # Unset (None, the parameter default): HVDTPU_QUANT=int8|fp8
        # arms the quantized wire. An explicit compression= — including
        # an explicit Compression.none — always wins over the env.
        q = _env.quant_mode()
        compression = Compression.by_name(q) if q else Compression.none
    quantized = is_quantized(compression)
    if quantized:
        # Pin the block size now so the optimizer's residual layout and
        # the lint prediction can never read different env values.
        compression = compression.with_block(compression.block_size())
    overlap = _env.overlap_default() if args.overlap is None else args.overlap
    accum_steps = args.accum_steps
    if accum_steps is None:
        accum_steps = _env.overlap_accum_steps()
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    stagger = args.stagger
    if stagger is None:
        # Default only arms chaining as part of the overlap pipeline; an
        # EXPLICIT stagger=True is honored standalone (measuring bucket
        # chaining without the scheduler compile options is legitimate).
        stagger = bool(overlap) and _env.overlap_stagger()
    lint_arg = _env.lint_mode() if args.lint is None else args.lint
    lint = "warn" if lint_arg is True else (lint_arg or "")
    if lint in ("off", "none", "no", "false", "0"):
        # Accept the documented HVDTPU_LINT spellings so a caller can
        # mirror the env value to force-disable over an env default.
        lint = ""
    if lint not in ("", "warn", "raise"):
        raise ValueError(
            "lint must be one of False/'off'/'warn'/'raise', "
            f"got {lint_arg!r}"
        )
    from ..guard import resolve as _guard_resolve
    from ..ops import actquant as _actquant
    from ..ops.remat import resolve_policy as _remat_policy

    remat = _env.remat_mode() if args.remat is None else args.remat
    compute_dtype = args.compute_dtype
    if compute_dtype is None:
        compute_dtype = _env.compute_dtype_mode()
    if compute_dtype not in ("", "fp8"):
        raise ValueError(
            f"compute_dtype={compute_dtype!r} is not recognized; "
            "use ''|'fp8'"
        )
    act_quant = _actquant.resolve_mode(args.act_quant)
    if compute_dtype == "fp8":
        if args.sharded:
            raise NotImplementedError(
                "compute_dtype='fp8' is replicated-path only: the ZeRO-1 "
                "flat-shard update cannot see which bucket slices are fp8 "
                "scale state, so the overwrite-with-gradient commit has "
                "no leaf boundary to mask on"
            )
        if args.op is not Average:
            raise ValueError(
                "compute_dtype='fp8' requires op=Average: the delayed-"
                "scaling state rides the gradient reduction, and only "
                "the mean keeps amax histories replica-uniform"
            )
    # Validate the policy now, before any tracing: the wrapped loss is
    # what accumulate_gradients differentiates, so the policy governs
    # every microbatch's backward identically.
    _remat_policy(remat)
    guard = _guard_resolve(args.guard)
    if args.distribute_optimizer and args.fused_update and not args.sharded:
        raise ValueError(
            "fused_update requires the ZeRO-1 flat-shard layout; "
            "pass sharded=True"
        )
    m = args.mesh if args.mesh is not None else ctx.mesh
    world_axes = ctx.world_axes
    batch_spec = args.batch_spec if args.batch_spec is not None else P(
        world_axes if len(world_axes) > 1 else world_axes[0]
    )
    overlapped_exchange = (
        args.distribute_optimizer and not args.sharded and not quantized
        and int(np.prod([m.shape[a] for a in _axis_or_world(args.axis)])) > 1
    )
    reduction_limit = (
        overlap_threshold_bytes(args.threshold_bytes) if overlapped_exchange
        else args.threshold_bytes
    )
    copts = None
    if overlap or overlapped_exchange:
        platform = m.devices.flat[0].platform
        copts = {
            **collective_compiler_options(reduction_limit, platform=platform),
            **overlap_compiler_options(platform),
        } or None
    publish = (
        _env.publish_every() if args.publish is None
        else max(0, int(args.publish))
    )
    return _Options(**vars(args) | dict(
        autotune=autotune, compression=compression, quantized=quantized,
        overlap=overlap, accum_steps=accum_steps, stagger=stagger, lint=lint,
        remat=remat, compute_dtype=compute_dtype, act_quant=act_quant,
        guard=guard, publish=publish, mesh=m, world_axes=world_axes,
        batch_spec=batch_spec, overlapped_exchange=overlapped_exchange,
        reduction_limit=reduction_limit, copts=copts,
    ))


def _attach_autotuner(args, o):
    """The step wrapped in the worker half of the knob search, or None
    when every live knob is pinned by this build. The tuner builds, and
    after a retrace switch rebuilds, from the arguments AS GIVEN with
    ``autotune=False``: the switch has written new knob values to the
    environment and the rebuild must resolve against them."""
    from .. import tune as _tune

    ctx = _get_context()
    pinned = []
    if args.threshold_bytes is not None:
        pinned.append(_env.FUSION_THRESHOLD)
    if args.compute_dtype is not None:
        pinned.append(_env.COMPUTE_DTYPE)
    if args.act_quant is not None:
        pinned.append(_env.ACT_QUANT)
    if args.stagger is not None or not o.overlap:
        # Explicitly pinned, or inert without the overlap pipeline
        # (its env default only arms as part of overlap) — either
        # way tuning it would score noise.
        pinned.append(_env.OVERLAP_STAGGER)
    untuned = dataclasses.replace(args, autotune=False)
    return _tune.attach_train_autotuner(
        lambda: _build(_resolve(untuned)),
        o.autotune,
        pinned=pinned,
        mesh_shape={a: ctx.mesh.shape[a] for a in ctx.mesh.axis_names},
        cross_axes=tuple(ctx.cross_axes or ()),
        structure_locked=bool(
            o.sharded or o.fused_update
            or (o.quantized and o.error_feedback)
        ),
    )


def _build(o):
    """The untuned build: optimizer, program, static surfaces, host
    wrappers. Returns ``(step, wrapped_optimizer)``."""
    opt = _build_optimizer(o)
    program = _build_program(opt, o)
    return _wrap(program, _static_surfaces(program, o), o), opt


def _build_optimizer(o):
    """The optimizer the step updates with: the caller's, split for fp8
    state where armed, behind the replicated or the ZeRO-1 wrapper."""
    from ..ops.fp8 import fp8_state_optimizer

    optimizer = o.optimizer
    if o.compute_dtype == "fp8":
        # Masked optimizer split BEFORE the distributed wrapper: fp8_*
        # leaves commit their gradient-carried new values verbatim (no
        # Adam moments), every other leaf sees the base optimizer. A
        # harmless no-op when the model declares no fp8 state.
        optimizer = fp8_state_optimizer(optimizer)
    if not o.distribute_optimizer:
        return optimizer
    wire = dict(
        op=o.op, compression=o.compression, axis=o.axis,
        threshold_bytes=o.threshold_bytes, stagger=o.stagger,
        error_feedback=o.error_feedback,
    )
    if o.sharded:
        return ShardedDistributedOptimizer(
            optimizer, gather_compression=o.gather_compression,
            fused_update=o.fused_update, **wire,
        )
    return DistributedOptimizer(optimizer, **wire)


@dataclasses.dataclass(frozen=True)
class _Program:
    """The traced and compiled program of one build, and nothing of the
    host planes."""

    dispatch: Callable  # (state, batch) -> (state, loss[, aux])
    mapped_for: Callable  # state -> the shard_map'd step, before jit
    jitted_for: Callable  # state -> the jax.jit that dispatch calls
    # state (arrays or shapes) -> the state where dispatch puts it
    as_dispatched: Callable = lambda state: state


def _build_program(opt, o) -> _Program:
    """Everything that decides the traced and the compiled program: the
    loss's wrapping, ``hvd_train_step``, ``shard_map``, ``jit`` with the
    exchange's compiler options, and the placing of a state that is not
    on the mesh. Knows none of the host planes."""
    from ..guard import check_gradients as _guard_check
    from ..ops import actquant as _actquant
    from ..ops.remat import checkpoint_fn as _remat_wrap

    has_aux, axis, guard_cfg, m = o.has_aux, o.axis, o.guard, o.mesh
    if o.act_quant:
        def _armed_loss(params, batch):
            # Arm the model-side boundaries for exactly this trace; the
            # thread-local keeps concurrently-traced plain steps plain.
            with _actquant.activate(o.act_quant):
                return o.loss_fn(params, batch)

        loss_fn = _actquant.checkpoint_fn(_armed_loss, o.remat, o.act_quant)
    else:
        loss_fn = _remat_wrap(o.loss_fn, o.remat)

    # The jitted function has a name of its own, so its builds are not
    # mixed with anything else called ``_step`` (obs/build.py), and it
    # names its phases: every operation of the compiled step lies in
    # exactly one of ``hvd_grad`` (JAX marks forward against backward
    # inside it: ``jvp(...)`` / ``transpose(jvp(...))``), ``hvd_reduce``
    # and ``hvd_update`` (both opened inside ``opt.update``, which does
    # both: optimizer.py) and ``hvd_loss_avg``.
    def hvd_train_step(state: TrainState, batch):
        with jax.named_scope("hvd_grad"):
            loss, aux, grads = accumulate_gradients(
                loss_fn, state.params, batch, o.accum_steps, has_aux=has_aux
            )
        guard = state.guard
        if guard_cfg is not None:
            # In-graph gradient guard: screen BEFORE anything commits.
            # The update (and its collectives) still executes
            # unconditionally — collectives must never sit under
            # data-dependent control flow — but the commit is selected
            # by the replica-uniform verdict, so a poisoned step leaves
            # params/opt-state/EF-residuals untouched and the step
            # counter does not advance (the pipeline retries).
            with jax.named_scope("hvd_grad"):
                ok, _gnorm, guard = _guard_check(
                    grads, state.guard, guard_cfg, axis=axis
                )
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        with jax.named_scope("hvd_update"):
            params = optax.apply_updates(state.params, updates)
            if guard_cfg is not None:
                params, opt_state = guarded_commit(
                    ok, params, opt_state, state.params, state.opt_state
                )
        with jax.named_scope("hvd_loss_avg"):
            loss = allreduce(loss, op=Average, axis=axis)
        advance = 1 if guard_cfg is None else ok.astype(state.step.dtype)
        new_state = TrainState(
            params, opt_state, state.step + advance, state.extra, guard
        )
        return (new_state, loss, aux) if has_aux else (new_state, loss)

    def mapped(state_spec):
        out_specs = (state_spec, P(), P()) if has_aux else (state_spec, P())
        return jax.shard_map(
            hvd_train_step, mesh=m, in_specs=(state_spec, o.batch_spec),
            out_specs=out_specs, check_vma=False,
        )

    def jitted(mapped_step):
        return jax.jit(
            mapped_step,
            donate_argnums=(0,) if o.donate else (),
            compiler_options=o.copts,
        )

    # The replicated-without-EF step has structure-independent specs;
    # the sharded path AND the quantized-with-error-feedback replicated
    # path carry dim-0-sharded flat buffers (opt-state buckets / EF
    # residuals) whose specs depend on the state's structure.
    if o.sharded or (
        o.quantized and o.error_feedback and o.distribute_optimizer
    ):
        return _program_per_structure(mapped, jitted, axis)
    step_mapped = mapped(P())
    step_jitted = jitted(step_mapped)
    program = _Program(
        step_jitted, lambda state: step_mapped, lambda state: step_jitted
    )
    if not o.overlapped_exchange or m.is_multi_process:
        return program
    # The step returns its state replicated over the mesh. A state that
    # arrives otherwise (``init_state`` leaves it on the default device)
    # makes the second call build a second program for the new input
    # shardings (ROADMAP D1b), and the overlapped exchange's programs
    # are the larger ones to build and to load: such a state is placed
    # before it is dispatched, and ``step.lower`` lowers for that place.
    on_mesh = NamedSharding(m, P())

    def placed(state: TrainState, put):
        at = getattr(state.step, "sharding", None)
        if at is not None and at.is_equivalent_to(on_mesh, 0):
            return state
        return jax.tree.map(lambda x: put(x, on_mesh), state)

    def abstract(x, sharding):
        return jax.ShapeDtypeStruct(
            np.shape(x), jnp.result_type(x), sharding=sharding
        )

    return dataclasses.replace(
        program,
        dispatch=lambda state, batch: step_jitted(
            placed(state, jax.device_put), batch
        ),
        as_dispatched=lambda state: placed(state, abstract),
    )


def _program_per_structure(mapped, jitted, axis) -> _Program:
    """Structure-dependent path: the opt-state specs depend on the
    state's structure (which flat buckets the params pack into), so
    the shard_map is built lazily on first call and cached per state
    treedef. The specs shard every FlatBuckets buffer (ZeRO-1 bucket
    or EF residual) dim-0 over the world axis — the global view of the
    state is the full padded buffer, each device holds its 1/N slice,
    and donation of the TrainState works exactly as in the plain path."""
    cache = {}

    def mapped_for(state: TrainState):
        return mapped(TrainState(
            P(),
            sharded_state_specs(state.opt_state, axis=axis),
            P(),
            P(),
            P(),  # guard scalars (empty subtree when unguarded)
        ))

    def jitted_for(state: TrainState):
        key = jax.tree.structure(state)
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jitted(mapped_for(state))
        return fn

    return _Program(
        lambda state, batch: jitted_for(state)(state, batch),
        mapped_for, jitted_for,
    )


def _static_surfaces(program: _Program, o) -> dict:
    """``lint`` / ``memplan`` / ``trace`` / ``certify`` / ``lower`` of the
    as-built step, by the names they take on it: each traces or lowers
    the exact program and executes nothing, so all are safe on live
    (donatable) state. ``jaxpr=`` reuses a caller-held trace, so sweep
    callers trace once per variant and share it between them."""
    world = int(np.prod([o.mesh.shape[a] for a in o.world_axes]))
    donate_argnums = (0,) if o.donate else ()
    # One description of the build; ``memplan`` and ``certify`` each cut
    # their ``meta`` from it, every key and value as it was.
    built = {
        "sharded": o.sharded,
        "accum_steps": o.accum_steps,
        "overlap": bool(o.overlap),
        "quant": (
            getattr(getattr(o.compression, "spec", None), "name", "")
            if o.quantized else ""
        ),
        "remat": str(o.remat or ""),
        "compute_dtype": o.compute_dtype,
        "act_quant": o.act_quant,
    }

    def seeded(state):
        if o.guard is not None and state.guard is None:
            # These surfaces trace the step directly, before the guard
            # wrapper's first-call seeding has run — give the trace the
            # same seeded structure the wrapper would.
            from ..guard import fresh_state as _guard_fresh

            state = TrainState(
                state.params, state.opt_state, state.step, state.extra,
                _guard_fresh(),
            )
        return state

    def lint(state, batch, jaxpr=None, memory=None):
        """The static passes over the exact mapped program; ``memory``
        overrides the env-derived memory gate."""
        from .. import analysis as _analysis

        state = seeded(state)
        if memory is None:
            # The memory pass always runs with step.lint: oom-risk gates
            # only when a budget is declared (HVDTPU_HBM_BUDGET_GB), and
            # donation-missed-reuse is structural (a properly-donating
            # step has no candidates).
            memory = _analysis.MemoryLintConfig(
                budget_bytes=_env.hbm_budget_bytes()
            )
        return _analysis.lint_traced(
            program.mapped_for(state),
            (state, batch),
            donate_argnums=donate_argnums,
            declared_axes=set(o.mesh.axis_names),
            params=state.params,
            sharded=o.sharded,
            threshold_bytes=o.threshold_bytes,
            world=world,
            allow_low_precision_collectives=(
                o.compression is not Compression.none
                or o.gather_compression is not Compression.none
            ),
            allowlist=tuple(o.lint_allow),
            jaxpr=jaxpr,
            quant=o.compression if o.quantized else None,
            compute_dtype=o.compute_dtype,
            act_quant=o.act_quant,
            wire_dtype=getattr(o.compression, "wire_dtype", None),
            gather_wire_dtype=getattr(
                o.gather_compression, "wire_dtype", None
            ),
            memory=memory,
        )

    def memplan(state, batch, jaxpr=None):
        """Static per-device HBM plan of the exact as-built step (see
        :mod:`horovod_tpu.analysis.memory`) — the number every ROADMAP
        memory bet is priced against. Publishes ``memplan.peak_bytes``
        when the metrics plane is on."""
        from .. import analysis as _analysis

        state = seeded(state)
        plan = _analysis.plan_traced(
            program.mapped_for(state),
            (state, batch),
            donate_argnums=donate_argnums,
            world=world,
            jaxpr=jaxpr,
            meta={**built, "donate": o.donate},
        )
        _analysis.publish_peak_bytes(plan)
        return plan

    def trace(state, batch):
        state = seeded(state)
        return jax.make_jaxpr(program.mapped_for(state))(state, batch)

    def certify(state, batch, jaxpr=None):
        """Fingerprint the exact as-built program (see
        :mod:`horovod_tpu.analysis.certify`): the collective schedule of
        the traced jaxpr plus the predicted wire layout, hashed into a
        cross-rank-comparable ``ScheduleCert``."""
        from .. import analysis as _analysis
        from ..ops.fusion import bucket_byte_layout, quantized_bucket_layout

        state = seeded(state)
        if jaxpr is None:
            jaxpr = trace(state, batch)
        if o.quantized:
            wire = [
                dict(b)
                for b in quantized_bucket_layout(
                    state.params, o.threshold_bytes,
                    world=world, compression=o.compression,
                )
            ]
        else:
            wire = [
                [d, int(n)]
                for d, n in bucket_byte_layout(
                    state.params, o.reduction_limit
                )
            ]
        return _analysis.schedule_cert(
            jaxpr,
            world=world,
            wire=wire,
            meta={
                k: built[k]
                for k in ("sharded", "overlap", "accum_steps", "quant",
                          "compute_dtype", "act_quant", "remat")
            },
        )

    def lower(state, batch):
        """The jax.stages.Lowered of the exact jitted program this step
        dispatches (same donation, same compiler options):
        ``.compile().as_text()`` is its HLO, ``.memory_analysis()`` its
        device memory. Arrays or ShapeDtypeStructs; nothing executes."""
        state = program.as_dispatched(seeded(state))
        return program.jitted_for(state).lower(state, batch)

    return dict(
        lint=lint, memplan=memplan, trace=trace, certify=certify, lower=lower
    )


def _streamed(inner: Callable, publisher, every: int) -> Callable:
    """``inner`` followed by the weight stream's cadence check. It runs
    on a host-side step counter anchored once, so off-cadence steps pay
    no device sync; the authoritative version stamp is the real
    committed step, read only on cadence hits."""
    clock = {"base": None, "n": 0}

    def streamed(state, batch):
        out = inner(state, batch)
        new_state = out[0]
        if clock["base"] is None:
            # One host sync, first step only: anchor the cadence
            # clock to the real (possibly resumed-from-ckpt) step.
            clock["base"] = int(new_state.step) - 1
        clock["n"] += 1
        hint = clock["base"] + clock["n"]
        if hint % every == 0:
            # The device sync is already being paid on cadence
            # hits — use it to catch an elastic restore / guard
            # walk-back that moved state.step since the anchor,
            # and re-anchor so the host clock tracks the real
            # committed step again (a silently desynced hint
            # would stop ever hitting the true cadence).
            real_step = int(new_state.step)
            if real_step != hint:
                clock["base"] = real_step - clock["n"]
            # Off-cadence real steps fall through to the flush
            # path inside maybe_publish: nothing is captured,
            # but pendings keep draining.
            publisher.maybe_publish(new_state.params, real_step)
        elif publisher._pending:
            # Something is queued behind the guard gate or a KV
            # outage: retry the flush each step until it drains.
            publisher.flush()
        return out

    return streamed


def _wrap(program: _Program, surfaces: dict, o) -> Callable:
    """The host planes around the program, innermost first: jit span,
    lint, preflight, guard, weight stream, metrics. The order is a
    correctness condition, not a list to append to: lint traces the
    program and not the guard's retry loop; the guard runtime sits inside
    the metrics bracket, so instrumented timings see the guarded step end
    to end; the publisher reads the audit verdict and must not be
    audited. Then the step's attributes."""
    from ..obs import trace as _trace

    # Innermost: JAX's own dispatch of the jitted function, as a span
    # and (on by default, one perf_counter pair) a histogram; what
    # ``hvd.step.dispatch`` takes beyond it is this module's wrappers.
    def jit_call(state, batch):
        with _trace.span("hvd.step.jit", "train"):
            t0 = time.perf_counter()
            out = program.dispatch(state, batch)
            _obs.always().histogram("step.jit_dispatch_ms").observe(
                (time.perf_counter() - t0) * 1e3
            )
        return out

    fn = jit_call
    if o.lint:
        from ..analysis import LintError
        from ..analysis import errors as _lint_errors

        linted = False

        def checked(state, batch):
            # First call lints BEFORE dispatch: tracing is pure, so
            # ERROR findings abort with the state buffers untouched
            # (donation has not run yet). The latch is only set
            # after a lint that did NOT raise — a retried call after
            # LintError (or a transient tracing failure) must lint
            # again, not dispatch the broken program unlinted.
            nonlocal linted
            if not linted:
                with _trace.span("hvd.step.lint", "train"):
                    findings = surfaces["lint"](state, batch)
                errs = _lint_errors(findings)
                if o.lint == "raise" and errs:
                    raise LintError(errs)
                linted = True
                for f in findings:
                    warnings.warn(f"hvdtpu lint: {f}", stacklevel=2)
            return jit_call(state, batch)

        fn = checked

    def preflight(state, batch, tag="", mode=None, jaxpr=None):
        """Cross-rank cert gate: publish this build's fingerprint to
        the elastic KV and verify all ranks match BEFORE the first
        dispatch (a mismatched world hangs at its first divergent
        collective with no diagnostics otherwise). No-op — beyond
        the env read — outside an elastic world."""
        if mode is None:
            mode = _env.cert_mode()
        if not mode:
            return None
        from ..elastic.worker import cert_channel

        channel = cert_channel()
        if channel is None:
            return None
        cert = surfaces["certify"](state, batch, jaxpr=jaxpr)
        return channel.preflight(cert, tag=tag, mode=mode)

    cert_latch = {"done": False}
    inner = fn

    def preflighted(state, batch):
        # Same first-call latch discipline as the lint hook: the
        # latch is only set after a preflight that did NOT raise, so
        # a retried call after CertMismatchError re-verifies instead
        # of dispatching the divergent program. The autotune retrace
        # path flips the latch itself and preflights under a trial
        # tag (tune.AutotunedStep) to avoid racing the pre-rebuild
        # KV entry.
        if not cert_latch["done"]:
            with _trace.span("hvd.step.preflight", "train"):
                preflight(state, batch)
            cert_latch["done"] = True
        return inner(state, batch)

    fn = preflighted
    guard_runtime = None
    if o.guard is not None:
        from ..guard import GuardRuntime

        guard_runtime = GuardRuntime(o.guard, sharded=o.sharded)
        fn = guard_runtime.wrap(fn)
    stream_publisher = None
    if o.publish > 0:
        from ..stream import WeightPublisher

        stream_publisher = WeightPublisher(
            publish_every=o.publish,
            guard_runtime=guard_runtime,
            threshold_bytes=o.threshold_bytes,
        )
        fn = _streamed(fn, stream_publisher, o.publish)
    # Always wrapped: the wrapper itself checks enablement per call,
    # so obs.enable()/disable() after the step is built take effect.
    wrapped = _instrument_step(
        fn, o.tokens_per_step, o.flops_per_step,
        overlap=bool(o.overlap), accum_steps=o.accum_steps,
        quantized=o.quantized and o.error_feedback,
        fp8=o.compute_dtype == "fp8",
    )
    # On-demand lint of the as-built step (CLI/harness entry point) and
    # its fellows, plus the mapped (pre-jit) program for custom static
    # analysis (horovod_tpu.analysis.trace_collectives and the parity
    # checks).
    vars(wrapped).update(
        surfaces,
        preflight=preflight,
        _cert_latch=cert_latch,
        _mapped_for=program.mapped_for,
        guard_config=o.guard,
        guard_runtime=guard_runtime,
        stream_publisher=stream_publisher,
    )
    return wrapped


def init_state(params, wrapped_optimizer, extra=None, guard=None) -> TrainState:
    """Create a TrainState from the optimizer returned by
    :func:`make_train_step`.

    ``guard=True`` (or a :class:`~horovod_tpu.guard.GuardConfig`) seeds
    the fail-silent guard bookkeeping eagerly — useful when the state's
    pytree structure must be final before the first step (checkpoint
    restore targets); a guarded step otherwise seeds it on first call.
    """
    gstate = None
    if guard:
        from ..guard import fresh_state as _guard_fresh

        gstate = _guard_fresh()
    return TrainState(
        params, wrapped_optimizer.init(params), jnp.zeros((), jnp.int32),
        extra, gstate,
    )
