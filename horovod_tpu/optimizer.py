"""Distributed optimizer & gradient transforms.

TPU-native re-design of the reference's optimizer wrappers:

* ``hvd.DistributedOptimizer`` (``horovod/tensorflow/__init__.py:568``,
  ``horovod/torch/optimizer.py:35-268``) — wraps a local optimizer so every
  step reduces gradients across workers before applying updates.
* ``hvd.DistributedGradientTape`` (``horovod/tensorflow/__init__.py:673``) —
  here :func:`grad` / :func:`value_and_grad`, returning allreduced grads.
* ``backward_passes_per_step`` local gradient aggregation
  (``horovod/tensorflow/gradient_aggregation.py:16``,
  ``horovod/torch/optimizer.py:170-198``).
* ``_DistributedAdasumOptimizer`` (``horovod/torch/optimizer.py:270``) —
  pass ``op=Adasum``.

The reference hooks per-gradient callbacks into autograd and negotiates
tensor readiness on a background thread; on TPU the whole training step is
one compiled SPMD program, so the wrapper is an ``optax``
``GradientTransformation`` that inserts a *fused, bucketed* allreduce
(:func:`horovod_tpu.ops.fusion.fused_allreduce`) in front of the inner
update — the fusion/negotiation cycle collapses into compile-time
structure.
"""

from __future__ import annotations

import warnings
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .context import _axis_or_world as _norm_axes, _in_trace, _traced_size
from .context import device_platform, size as _world_size
from .obs import registry as _obs
from .exceptions import HorovodTpuError
from .ops.adasum import adasum_allreduce_tree
from .ops.collectives import Adasum, Average, ReduceOp, Sum
from .ops.compression import Compression, is_quantized
from .ops.fusion import (
    EFResiduals,
    FlatBuckets,
    bucket_byte_layout,
    fused_allgather,
    fused_allreduce,
    fused_reducescatter,
    pack,
    quantized_fused_allreduce,
    quantized_fused_reducescatter,
    record_update_seams,
    shard_slice,
    unpack,
    update_seams,
)
from .utils import env as _env


class DistributedOptState(NamedTuple):
    inner: optax.OptState
    acc: Optional[optax.Updates]  # local gradient accumulator (bpps > 1)
    count: jnp.ndarray  # passes since last sync
    # Quantized-wire error-feedback residuals (EFResiduals, one fp32
    # buffer per fused bucket, rank-local — globally dim-0 sharded over
    # the world axis); None whenever compression is not quantized or
    # error feedback is off.
    residual: Optional[Any] = None


# -- fused optimizer update (ZeRO-1 hot loop) ---------------------------
#
# The sharded weight update's inner optax chain emits one elementwise HLO
# per Adam algebra step, each round-tripping the flat shard through HBM.
# ``fused_adamw`` carries the hyperparameters as static data so
# ``ShardedDistributedOptimizer(fused_update=True)`` can run the whole
# chain as ONE pass over each shard bucket — the Pallas kernel
# ``ops.pallas_kernels.fused_adamw_update_pallas`` on TPU, the bit-pinned
# pure-jax twin below elsewhere. State layout, init and the unfused
# update are optax.adamw verbatim, so checkpoints, canonicalization and
# ``fused_update=False`` interop unchanged.


class FusedAdamSpec(NamedTuple):
    """Static AdamW hyperparameters of a :func:`fused_adamw` optimizer —
    what the fused kernel bakes into its one compiled pass."""

    learning_rate: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    eps_root: float = 0.0
    weight_decay: float = 1e-4


class _FusedAdamW:
    """optax.adamw plus a ``fused_spec`` the sharded optimizer reads.

    Structurally a ``GradientTransformation`` (``init``/``update``
    delegate to the optax reference), so everything that consumes a plain
    optimizer — including ``fused_update=False`` — behaves identically.
    """

    def __init__(self, spec: FusedAdamSpec):
        self.fused_spec = spec
        self._ref = optax.adamw(
            spec.learning_rate, b1=spec.b1, b2=spec.b2, eps=spec.eps,
            eps_root=spec.eps_root, weight_decay=spec.weight_decay,
        )
        self.init = self._ref.init
        self.update = self._ref.update

    def __repr__(self):
        return f"fused_adamw({self.fused_spec})"


def fused_adamw(
    learning_rate: float,
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    eps_root: float = 0.0,
    weight_decay: float = 1e-4,
) -> _FusedAdamW:
    """``optax.adamw`` that additionally supports the fused ZeRO-1 update
    (``ShardedDistributedOptimizer(fused_update=True)`` /
    ``HVDTPU_FUSED_UPDATE=1``). The learning rate must be a static float:
    the fused kernel bakes the hyperparameters into its single compiled
    pass (schedules stay on the unfused path — pass ``optax.adamw``)."""
    if callable(learning_rate):
        raise ValueError(
            "fused_adamw needs a static float learning rate (the fused "
            "kernel bakes it in); use optax.adamw for schedules"
        )
    return _FusedAdamW(
        FusedAdamSpec(
            float(learning_rate), float(b1), float(b2), float(eps),
            float(eps_root), float(weight_decay),
        )
    )


def _fused_adamw_update_jax(p, m, v, g, count, spec: FusedAdamSpec):
    """Pure-jax twin of ``fused_adamw_update_pallas`` — IDENTICAL op
    order (the fast-tier CPU-interpreter parity test pins the two
    bit-for-bit). Math in fp32 regardless of buffer dtypes; only the
    outputs cast back — the update lands in ``p.dtype`` (the bf16 "param
    cast" of the fused pass), the moments keep their storage dtypes."""
    c = (jnp.asarray(count, jnp.int32) + 1).astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    nm = (1.0 - spec.b1) * g32 + spec.b1 * m.astype(jnp.float32)
    nv = (1.0 - spec.b2) * (g32 * g32) + spec.b2 * v.astype(jnp.float32)
    mhat = nm / (1.0 - spec.b1 ** c)
    vhat = nv / (1.0 - spec.b2 ** c)
    u = mhat / (jnp.sqrt(vhat + spec.eps_root) + spec.eps)
    if spec.weight_decay:
        u = u + spec.weight_decay * p32
    return (
        (-spec.learning_rate * u).astype(p.dtype),
        nm.astype(m.dtype),
        nv.astype(v.dtype),
    )


def fused_adamw_update(
    p, m, v, g, count, spec: FusedAdamSpec, *, impl: Optional[str] = None
):
    """One fused AdamW step over flat 1-D buffers: ``(update, new_m,
    new_v)``. ``impl`` forces ``"jax"``/``"pallas"`` (default: Pallas on
    TPU, the twin elsewhere — the quantize_blockwise dispatch rule)."""
    use_pallas = (
        impl == "pallas" if impl else device_platform() == "tpu"
    )
    if use_pallas:
        from .ops.pallas_kernels import fused_adamw_update_pallas

        return fused_adamw_update_pallas(
            p, m, v, g, count, lr=spec.learning_rate, b1=spec.b1,
            b2=spec.b2, eps=spec.eps, eps_root=spec.eps_root,
            weight_decay=spec.weight_decay,
        )
    return _fused_adamw_update_jax(p, m, v, g, count, spec)


def _is_adam_node(s) -> bool:
    return all(hasattr(s, f) for f in ("count", "mu", "nu", "_replace"))


def _record_fused_update(n_buffers: int) -> None:
    if not _obs.enabled():
        return
    reg = _obs.metrics()
    reg.gauge("optimizer.fused_update").set(1.0)
    reg.gauge("optimizer.fused_update_buckets").set(n_buffers)


def _fused_flat_update(g_shards, inner, p_shards, spec: FusedAdamSpec):
    """Apply the fused AdamW pass bucket-by-bucket over the flat shard
    layout, rebuilding the inner optax state with its exact structure
    (``ScaleByAdamState`` count/mu/nu replaced, everything else passed
    through) so checkpoints cannot tell fused and unfused states apart.
    """
    if not isinstance(inner, tuple):
        raise HorovodTpuError(
            "fused_update expects the optax.adamw chain state (a tuple); "
            f"got {type(inner).__name__}"
        )
    adam_nodes = [s for s in inner if _is_adam_node(s)]
    if len(adam_nodes) != 1 or not isinstance(adam_nodes[0].mu, FlatBuckets):
        raise HorovodTpuError(
            "fused_update could not find the flat-bucket Adam moments in "
            "the optimizer state; build the optimizer with "
            "horovod_tpu.fused_adamw(...) and sharded=True"
        )
    adam = adam_nodes[0]
    out_u, out_m, out_v = [], [], []
    for p, m, v, g in zip(
        p_shards.buffers, adam.mu.buffers, adam.nu.buffers, g_shards.buffers
    ):
        u, nm, nv = fused_adamw_update(p, m, v, g, adam.count, spec)
        out_u.append(u)
        out_m.append(nm)
        out_v.append(nv)
    _record_fused_update(len(out_u))
    new_adam = adam._replace(
        count=optax.safe_int32_increment(adam.count),
        mu=FlatBuckets(out_m),
        nu=FlatBuckets(out_v),
    )
    new_inner = tuple(new_adam if s is adam else s for s in inner)
    return FlatBuckets(out_u), new_inner


def _resolve_quant(compression, threshold_bytes):
    """Pin a quantized compressor's block size and the fusion threshold
    at optimizer construction: the EF residual layout is state, so a
    later change of the env knobs must not desync it from the live
    buffers. Returns ``(compression, threshold_bytes, quantized)``."""
    if not is_quantized(compression):
        return compression, threshold_bytes, False
    compression = compression.with_block(compression.block_size())
    if threshold_bytes is None:
        threshold_bytes = _env.fusion_threshold_bytes()
    return compression, threshold_bytes, True


def _init_residuals(params, threshold_bytes, block, axes) -> EFResiduals:
    """Zero EF residuals in the bucket layout quantized collectives pack
    (padded to ``world * block``). Inside the SPMD region each rank
    builds its local ``[padded]`` buffer; outside, the global
    ``[world * padded]`` view the train step's in_specs shard."""
    layout = bucket_byte_layout(
        params, threshold_bytes,
        pad_multiple=_world_or_traced(axes) * block,
    )
    in_trace = _in_trace(axes)
    world = 1 if in_trace else _world_or_traced(axes)
    bufs = [
        jnp.zeros(
            (world * (nbytes // np.dtype(dt).itemsize),), jnp.float32
        )
        for dt, nbytes in layout
    ]
    return EFResiduals(
        bufs, threshold=threshold_bytes or 0, block=block
    )


def _world_or_traced(axes) -> int:
    return _traced_size(axes) if _in_trace(axes) else _world_size(axes)


def _record_grad_bytes(grads) -> None:
    """Trace-time gauge of the gradient payload one optimizer update
    reduces (leaf bytes, pre-compression) — the optimizer-level view the
    per-collective fusion gauges roll up into."""
    if not _obs.enabled():
        return
    from .ops.fusion import leaf_nbytes

    total = sum(leaf_nbytes(l) for l in jax.tree.leaves(grads))
    reg = _obs.metrics()
    reg.gauge("optimizer.grad_bytes_per_step").set(total)
    reg.counter("optimizer.reduce_traces").inc()


def _reduce_grads(grads, op, compression, prescale, postscale, axis, threshold,
                  stagger=False):
    _record_grad_bytes(grads)
    if op == Adasum:
        return adasum_allreduce_tree(grads, axis=axis)
    return fused_allreduce(
        grads,
        op=op,
        prescale_factor=prescale,
        postscale_factor=postscale,
        axis=axis,
        threshold_bytes=threshold,
        compression=compression,
        stagger=stagger,
    )


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: ReduceOp = Average,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    average_aggregated_gradients: bool = False,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    axis=None,
    threshold_bytes: Optional[int] = None,
    sharded: bool = False,
    gather_compression=Compression.none,
    stagger: bool = False,
    error_feedback: bool = True,
    fused_update: Optional[bool] = None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer with cross-worker gradient reduction.

    Use inside a sharded train step (``horovod_tpu.spmd`` /
    ``parallel.dp.make_train_step``); each worker computes gradients on its
    shard, the wrapper performs one fused allreduce per ≤128 MB bucket, then
    the inner optimizer applies identical updates on every worker.

    Args mirror the reference wrapper: ``compression`` (fp16/bf16 wire
    format), ``op`` (Average/Sum/Adasum), ``backward_passes_per_step`` (only
    every k-th step pays the allreduce; gradients accumulate locally in
    between), ``prescale_factor``/``postscale_factor`` (fused scaling,
    ``operations.cc:943-958``).

    ``sharded=True`` selects the ZeRO-1 sharded weight update
    (:func:`ShardedDistributedOptimizer`): reduce-scatter instead of
    allreduce, 1/N optimizer state and update FLOPs per replica, and an
    all-gather of the updates (``gather_compression`` compresses that
    leg's transport).

    ``stagger`` chains the per-bucket collectives in readiness order for
    the overlap pipeline (``parallel.dp.make_train_step(overlap=True)``
    sets it); numerically the identity.

    ``compression=Compression.int8`` / ``Compression.fp8`` (or the
    ``HVDTPU_QUANT`` env default, resolved by ``dp.make_train_step``)
    selects the blockwise-quantized wire: the fused reduction lowers to
    a quantized all-to-all + all-gather at ring-allreduce byte parity
    (~2x below bf16; see ``ops/fusion.quantized_fused_allreduce``), and
    per-bucket **error-feedback residuals** become part of the optimizer
    state — this rank's quantization error, added back into the next
    step's gradient so no gradient mass is lost, only delayed.
    ``error_feedback=False`` drops the residuals (wire format unchanged;
    convergence degrades at aggressive block sizes — the on/off pair is
    measured in ``tests/test_quantization.py``).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    if sharded:
        if backward_passes_per_step != 1:
            raise NotImplementedError(
                "sharded=True does not support backward_passes_per_step > 1"
            )
        return ShardedDistributedOptimizer(
            optimizer,
            op=op,
            compression=compression,
            gather_compression=gather_compression,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            axis=axis,
            threshold_bytes=threshold_bytes,
            stagger=stagger,
            error_feedback=error_feedback,
            fused_update=fused_update,
        )
    if fused_update:
        raise NotImplementedError(
            "fused_update requires the ZeRO-1 flat-shard layout; pass "
            "sharded=True"
        )
    if fused_update is None and _env.fused_update_default():
        # Mirror the sharded path's incompatible-optimizer behavior: the
        # env default must degrade loudly, never silently — an operator
        # reading benchmark numbers has to know fusion is NOT active.
        warnings.warn(
            "HVDTPU_FUSED_UPDATE=1 ignored: the fused optimizer update "
            "requires the ZeRO-1 sharded path (sharded=True)",
            stacklevel=2,
        )
    compression, threshold_bytes, quantized = _resolve_quant(
        compression, threshold_bytes
    )
    if quantized and op not in (Average, Sum):
        raise ValueError("quantized compression supports op=Average/Sum")
    if quantized and backward_passes_per_step != 1:
        raise NotImplementedError(
            "quantized compression does not support "
            "backward_passes_per_step > 1 (the quantized collectives "
            "would nest under the sync cond; accumulate with "
            "dp.make_train_step(accum_steps=K) instead)"
        )
    ef = quantized and error_feedback
    bpps = backward_passes_per_step

    def init(params):
        acc = None if bpps == 1 else jax.tree.map(jnp.zeros_like, params)
        residual = (
            _init_residuals(
                params, threshold_bytes, compression.block_size(),
                _norm_axes(axis),
            )
            if ef
            else None
        )
        return DistributedOptState(
            inner=optimizer.init(params), acc=acc,
            count=jnp.zeros((), jnp.int32), residual=residual,
        )

    # The phases of a step name themselves in the compiled program:
    # ``hvd_reduce`` is the gradient exchange, ``hvd_update`` the inner
    # optimizer (dp._step adds ``hvd_grad`` and ``hvd_loss_avg``). The
    # scopes reach the device trace through each instruction's op_name.
    def reduce_then_update(grads, inner, params):
        # The exchange, then the seam it gives between a gradient and its
        # update, kept for large leaves where nothing is exchanged
        # (ops/fusion.update_seams), then the update.
        with jax.named_scope("hvd_reduce"):
            reduced = _reduce_grads(
                grads, op, compression, prescale_factor, postscale_factor,
                axis, threshold_bytes, stagger,
            )
            if op == Adasum:
                record_update_seams()
            else:
                reduced, params = update_seams(reduced, params, axis=axis)
        with jax.named_scope("hvd_update"):
            return optimizer.update(reduced, inner, params)

    def update(grads, state: DistributedOptState, params=None):
        if quantized:
            with jax.named_scope("hvd_reduce"):
                reduced, new_res = quantized_fused_allreduce(
                    grads,
                    state.residual,
                    op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    axis=axis,
                    threshold_bytes=threshold_bytes,
                    compression=compression,
                    stagger=stagger,
                )
            _record_grad_bytes(grads)
            record_update_seams()
            with jax.named_scope("hvd_update"):
                updates, inner = optimizer.update(
                    reduced, state.inner, params
                )
            return updates, DistributedOptState(
                inner, None, state.count + 1, new_res
            )
        if bpps == 1:
            updates, inner = reduce_then_update(grads, state.inner, params)
            return updates, DistributedOptState(inner, None, state.count + 1)

        with jax.named_scope("hvd_grad"):
            acc = jax.tree.map(jnp.add, state.acc, grads)
        count = state.count + 1
        do_sync = (count % bpps) == 0

        def sync_branch(operands):
            acc_, inner_ = operands
            agg = acc_
            if average_aggregated_gradients:
                with jax.named_scope("hvd_reduce"):
                    agg = jax.tree.map(lambda g: g / bpps, agg)
            updates, new_inner = reduce_then_update(agg, inner_, params)
            with jax.named_scope("hvd_update"):
                zeroed = jax.tree.map(jnp.zeros_like, acc_)
            return updates, new_inner, zeroed

        def skip_branch(operands):
            acc_, inner_ = operands
            updates = jax.tree.map(jnp.zeros_like, acc_)
            return updates, inner_, acc_

        updates, inner, acc = jax.lax.cond(
            do_sync, sync_branch, skip_branch, (acc, state.inner)
        )
        return updates, DistributedOptState(inner, acc, count)

    return optax.GradientTransformation(init, update)


class ShardedOptState(NamedTuple):
    """State of :func:`ShardedDistributedOptimizer`.

    ``inner`` is the wrapped optimizer's state built over the flat fused
    bucket layout (:class:`~horovod_tpu.ops.fusion.FlatBuckets` leaves).
    Inside the SPMD region each replica holds the 1/N shard of every
    bucket; the global (outside-``shard_map``) view of the same arrays is
    the full padded bucket, dim 0 sharded over the world axis.

    ``threshold`` and ``world`` make the state self-describing: the
    fusion threshold that produced the bucket layout and the world size
    the padding was computed for ride along as scalar leaves, so
    checkpoint/elastic canonicalization reconstructs the exact layout
    without guessing the env knob the optimizer was built with.
    """

    inner: Any
    count: jnp.ndarray
    threshold: jnp.ndarray  # fusion threshold bytes (layout recipe)
    world: jnp.ndarray  # world size the bucket padding was built for
    # Quantization block the bucket padding was built for: buckets pad
    # to world*block (1 = unquantized). Recorded even when error
    # feedback is off — the canonical transforms must recover the exact
    # padded layout without consulting env knobs or residuals.
    block: jnp.ndarray = None
    # Quantized-wire EF residuals (EFResiduals; None when unquantized or
    # error_feedback=False). Each buffer is globally [world * padded] —
    # every rank's full-bucket residual — while the inner flat buckets
    # are globally [padded] (1/N per rank); both shard dim 0 over the
    # world axis.
    residual: Optional[Any] = None


class CanonicalOptState(NamedTuple):
    """World-size-portable form of :class:`ShardedOptState`.

    Flat buckets are unpacked back into parameter-shaped leaves (wrapped
    in :class:`CanonicalBuckets`), with the world-size-dependent padding
    stripped — what checkpoints store (gather-on-save) so a restore can
    re-pack for any world size (reshard-on-restore). ``threshold``
    carries the bucket-layout recipe forward. ``residual`` holds the
    EF residuals' canonical form: a :class:`CanonicalResiduals` wrapping
    the *mean-equivalent* residual (``sum over ranks / world``) unpacked
    to parameter shape — on restore every rank of the new world receives
    this value, which preserves the residuals' exact effect on the
    Average-reduced gradient across an N→M rescale.
    """

    inner: Any
    count: Any
    threshold: Any
    block: Any = None  # quantization block of the padded layout (1 = none)
    residual: Optional[Any] = None


class CanonicalDistOptState(NamedTuple):
    """Canonical (world-size-portable) form of a quantized
    :class:`DistributedOptState`: ``inner``/``acc`` are replicated and
    pass through; the EF residuals canonicalize exactly like the sharded
    path's (see :class:`CanonicalOptState`)."""

    inner: Any
    acc: Any
    count: Any
    residual: Any


class CanonicalResiduals:
    """Marker around the parameter-shaped mean-equivalent residual tree;
    ``threshold``/``block`` (static aux) carry the bucket-layout recipe
    the runtime :class:`~horovod_tpu.ops.fusion.EFResiduals` repack
    with."""

    def __init__(self, tree, threshold: int = 0, block: int = 0):
        self.tree = tree
        self.threshold = int(threshold)
        self.block = int(block)

    def __repr__(self):
        return f"CanonicalResiduals(block={self.block})"


jax.tree_util.register_pytree_node(
    CanonicalResiduals,
    lambda cr: ((cr.tree,), (cr.threshold, cr.block)),
    lambda aux, children: CanonicalResiduals(children[0], *aux),
)


class CanonicalBuckets:
    """Marker around a parameter-structured subtree that stands where a
    :class:`FlatBuckets` node stood — lets :func:`reshard_opt_state` find
    the re-pack boundaries structurally."""

    def __init__(self, tree):
        self.tree = tree

    def __repr__(self):
        return "CanonicalBuckets(...)"


jax.tree_util.register_pytree_node(
    CanonicalBuckets,
    lambda cb: ((cb.tree,), None),
    lambda aux, children: CanonicalBuckets(children[0]),
)


def _is_flat(n):
    return isinstance(n, FlatBuckets)


def _is_canonical(n):
    return isinstance(n, CanonicalBuckets)


def ShardedDistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: ReduceOp = Average,
    compression=Compression.none,
    gather_compression=Compression.none,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    axis=None,
    threshold_bytes: Optional[int] = None,
    stagger: bool = False,
    error_feedback: bool = True,
    fused_update: Optional[bool] = None,
) -> optax.GradientTransformation:
    """Cross-worker gradient reduction with a ZeRO-1 sharded weight update.

    The TPU-native improvement over the replicated wrapper
    (arXiv:2004.13336 "Automatic Cross-Replica Sharding of Weight Update
    in Data-Parallel Training"): gradients are packed into fused buckets
    padded to a multiple of the world size N, **reduce-scattered** so each
    replica owns a contiguous 1/N shard, the inner optax transformation
    runs on that shard only (1/N optimizer state and update FLOPs), and
    one **all-gather** of the updates restores the full tree for
    ``optax.apply_updates``. Collective wire bytes match the fused-psum
    path exactly (reduce-scatter + all-gather = one ring allreduce);
    optimizer-state memory and update compute drop by the world size.

    ``compression`` rides the reduce-scatter wire (the reference's
    fp16/bf16 gradient compression); ``gather_compression`` independently
    compresses the all-gather leg (the EQuARX-style low-precision
    transport of the updated values, arXiv:2506.17615) — updates move,
    not raw params, so a cast there behaves like update quantization.

    Constraints: the inner transformation must be **elementwise** (adam,
    adamw, sgd+momentum, …) — transforms that couple elements across the
    tree (``clip_by_global_norm``, layerwise LARS/LAMB) would see only
    the local shard. One world axis; ``update`` must run inside the SPMD
    region (``hvd.spmd`` / ``parallel.dp.make_train_step``); ``init``
    works both inside (returns the local 1/N shard) and outside (returns
    the global flat-bucket view, to be sharded by the train step's
    in_specs — what :func:`parallel.dp.init_state` relies on).

    ``fused_update=True`` (default reads ``HVDTPU_FUSED_UPDATE``) runs
    the inner update as ONE fused pass over each flat shard bucket —
    moment update, bias correction, weight decay, ``-lr`` scale and the
    param-dtype cast in a single Pallas kernel
    (:func:`~horovod_tpu.ops.pallas_kernels.fused_adamw_update_pallas`;
    bit-pinned pure-jax twin off-TPU) instead of the optax chain's
    one-HLO-per-step HBM round-trips. Requires the optimizer to carry
    static hyperparameters (:func:`fused_adamw`); state layout, init and
    checkpoints are identical to the unfused build. An explicit
    ``fused_update=True`` with an incompatible optimizer raises; the env
    default degrades to the unfused path with a warning.
    """
    if op not in (Average, Sum):
        raise ValueError(
            "ShardedDistributedOptimizer supports Average/Sum (Adasum's "
            "recursive halving has no scatter form here)"
        )
    # Pin the bucket layout at construction: init records this value in
    # the state and update packs with it, so a later change of the env
    # knob cannot desync the gradient layout from the live opt state.
    threshold_bytes = (
        threshold_bytes
        if threshold_bytes is not None
        else _env.fusion_threshold_bytes()
    )
    compression, threshold_bytes, quantized = _resolve_quant(
        compression, threshold_bytes
    )
    gather_compression, _, _ = _resolve_quant(gather_compression, None)
    if quantized and gather_compression is Compression.none:
        # One HVDTPU_QUANT/compression knob quantizes BOTH legs: a
        # quantized reduce-scatter with an fp32 update all-gather would
        # leave half the wire bytes on the table. An explicit
        # gather_compression still wins.
        gather_compression = compression
    ef = quantized and error_feedback
    # Fused-update resolution: an explicit True must not silently run
    # unfused (that would misreport every benchmark pair built on it),
    # while the env default has to tolerate optimizers that simply can't
    # fuse (schedules, non-adam chains).
    fused_explicit = fused_update is not None
    if fused_update is None:
        fused_update = _env.fused_update_default()
    fused_spec = getattr(optimizer, "fused_spec", None)
    if fused_update and fused_spec is None:
        if fused_explicit:
            raise HorovodTpuError(
                "fused_update=True needs an optimizer with static AdamW "
                "hyperparameters; build it with horovod_tpu.fused_adamw("
                "lr, ...) (optax schedules and non-adam chains run "
                "unfused)"
            )
        warnings.warn(
            "HVDTPU_FUSED_UPDATE=1 ignored: the inner optimizer carries "
            "no fused spec (use horovod_tpu.fused_adamw)",
            stacklevel=2,
        )
        fused_update = False
    # Chunk alignment: quantized buckets pad to world*block so every
    # all-to-all chunk is whole blocks; the unquantized layout pads to
    # the world size only.
    _pad_mult = (
        lambda world: world * compression.block_size()
        if quantized
        else world
    )

    def _axes():
        axes = _norm_axes(axis)
        if len(axes) != 1:
            raise HorovodTpuError(
                "sharded weight update supports a single world axis; got "
                f"{axes} (flatten the mesh or pass axis=<one name>)"
            )
        return axes

    def init(params):
        axes = _axes()
        if _in_trace(axes):
            world = _traced_size(axes)
            buffers, _ = pack(
                params, threshold_bytes, pad_multiple=_pad_mult(world)
            )
            inner = optimizer.init(shard_slice(buffers, axis=axes))
        else:
            world = _world_size(axes)
            buffers, _ = pack(
                params, threshold_bytes, pad_multiple=_pad_mult(world)
            )
            inner = optimizer.init(FlatBuckets(buffers))
        residual = (
            _init_residuals(
                params, threshold_bytes, compression.block_size(), axes
            )
            if ef
            else None
        )
        return ShardedOptState(
            inner=inner,
            count=jnp.zeros((), jnp.int32),
            threshold=jnp.asarray(threshold_bytes, jnp.int32),
            world=jnp.asarray(world, jnp.int32),
            block=jnp.asarray(
                compression.block_size() if quantized else 1, jnp.int32
            ),
            residual=residual,
        )

    def update(grads, state: ShardedOptState, params=None):
        if params is None:
            raise ValueError(
                "ShardedDistributedOptimizer.update requires params (the "
                "local param shard feeds the inner update)"
            )
        axes = _axes()
        if not _in_trace(axes):
            raise HorovodTpuError(
                "sharded update must run inside the SPMD region (wrap the "
                "step with horovod_tpu.spmd or use parallel.dp."
                "make_train_step(sharded=True))"
            )
        _record_grad_bytes(grads)
        record_update_seams()
        new_res = state.residual
        # Scopes as in DistributedOptimizer: the reduce-scatter and the
        # all-gather are both ``hvd_reduce``, the shard update between
        # them ``hvd_update``.
        with jax.named_scope("hvd_reduce"):
            if quantized:
                g_shards, spec, new_res = quantized_fused_reducescatter(
                    grads,
                    state.residual,
                    op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    axis=axes,
                    threshold_bytes=threshold_bytes,
                    compression=compression,
                    stagger=stagger,
                )
            else:
                g_shards, spec = fused_reducescatter(
                    grads,
                    op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    axis=axes,
                    threshold_bytes=threshold_bytes,
                    compression=compression,
                    stagger=stagger,
                )
        with jax.named_scope("hvd_update"):
            p_buffers, _ = pack(
                params, threshold_bytes,
                pad_multiple=_pad_mult(_traced_size(axes)),
            )
            if [int(b.shape[0]) for b in p_buffers] != list(
                spec.padded_sizes()
            ):
                raise HorovodTpuError(
                    "gradient and parameter bucket layouts differ "
                    f"({[int(b.shape[0]) for b in p_buffers]} vs "
                    f"{list(spec.padded_sizes())}); the sharded update "
                    "needs grads to pack like params (same tree, shapes "
                    "and dtypes — mixed grad/param precision is not "
                    "supported)"
                )
            p_shards = shard_slice(p_buffers, axis=axes)
            if fused_update:
                u_shards, inner = _fused_flat_update(
                    g_shards, state.inner, p_shards, fused_spec
                )
            else:
                u_shards, inner = optimizer.update(
                    g_shards, state.inner, p_shards
                )
        with jax.named_scope("hvd_reduce"):
            updates = fused_allgather(
                u_shards, spec, axis=axes, compression=gather_compression,
                stagger=stagger,
            )
        return updates, ShardedOptState(
            inner=inner,
            count=state.count + 1,
            threshold=state.threshold,
            world=state.world,
            block=state.block,
            residual=new_res,
        )

    return optax.GradientTransformation(init, update)


def guarded_commit(ok, new_params, new_opt_state, params, opt_state):
    """Commit or skip one optimizer step under the gradient guard
    (:mod:`horovod_tpu.guard`): returns ``(params, opt_state)`` — the
    freshly-computed pair when ``ok``, the *incoming* pair verbatim
    otherwise, selected via ``jax.lax.cond``.

    The update (and its collectives) always executes — collectives must
    never sit under data-dependent control flow, and ``ok`` is made
    replica-uniform upstream — only the *commit* is conditional.  The
    selection is structural over the whole state pair, so everything a
    poisoned step touched passes through unchanged on a skip: the inner
    optimizer moments, the ZeRO-1 flat buckets, and the quantized-wire
    EF residuals (which would otherwise absorb the quantization error
    of a gradient that was never applied).
    """
    return jax.lax.cond(
        ok,
        lambda op: (op[0], op[1]),
        lambda op: (op[2], op[3]),
        (new_params, new_opt_state, params, opt_state),
    )


# -- sharded-state layout transforms (checkpoint / elastic) -------------


def sharded_state_specs(opt_state, axis=None):
    """``PartitionSpec`` tree for a :class:`ShardedOptState` (or any
    state carrying flat-bucket leaves, e.g. a quantized
    :class:`DistributedOptState`'s EF residuals): flat-bucket buffers are
    dim-0 sharded over the world axis, everything else replicated. The
    container type is preserved (``EFResiduals`` aux rides along) so the
    spec tree structurally matches the state. Feed to
    ``shard_map``/``jit`` in/out specs (what ``make_train_step`` does for
    the sharded and quantized paths)."""
    from jax.sharding import PartitionSpec as P

    axes = _norm_axes(axis)
    a = axes if len(axes) > 1 else axes[0]

    def spec(n):
        if _is_flat(n):
            return jax.tree.map(lambda _: P(a), n)
        return P()

    return jax.tree.map(spec, opt_state, is_leaf=_is_flat)


def has_ef_residuals(tree) -> bool:
    """True when ``tree`` carries quantized-wire EF residual state."""
    leaves = jax.tree.flatten(
        tree, is_leaf=lambda n: isinstance(n, EFResiduals)
    )[0]
    return any(isinstance(l, EFResiduals) for l in leaves)


def ef_residual_norm(tree):
    """Global L2 norm of every EF residual in ``tree`` (None when the
    tree carries no residuals) — the ``quant.residual_norm`` gauge the
    instrumented train step exports."""
    sq = [
        jnp.sum(jnp.square(b.astype(jnp.float32)))
        for n in jax.tree.flatten(
            tree, is_leaf=lambda x: isinstance(x, EFResiduals)
        )[0]
        if isinstance(n, EFResiduals)
        for b in n.buffers
    ]
    if not sq:
        return None
    return float(jnp.sqrt(sum(sq)))


def _pack_spec_for(params, threshold_bytes=None):
    # Layout recipe only — same deterministic bucketing ``update`` uses.
    _, spec = pack(params, threshold_bytes)
    return spec


def has_sharded_state(tree) -> bool:
    """True when ``tree`` contains runtime state that must canonicalize
    before a world-size-portable save: ZeRO-1 flat buckets, or a
    quantized :class:`DistributedOptState` carrying EF residuals."""
    leaves = jax.tree.flatten(
        tree,
        is_leaf=lambda n: isinstance(
            n, (ShardedOptState, DistributedOptState)
        ),
    )[0]
    return any(
        isinstance(l, ShardedOptState)
        or (isinstance(l, DistributedOptState) and l.residual is not None)
        for l in leaves
    )


def has_canonical_state(tree) -> bool:
    """True when ``tree`` contains a canonical (checkpoint-form) state."""
    leaves = jax.tree.flatten(
        tree,
        is_leaf=lambda n: isinstance(
            n, (CanonicalOptState, CanonicalDistOptState)
        ),
    )[0]
    return any(
        isinstance(l, (CanonicalOptState, CanonicalDistOptState))
        for l in leaves
    )


def _canonicalize_residuals(
    residual, spec, world: int
) -> Optional[CanonicalResiduals]:
    """Runtime EF residuals (global ``[world * padded]`` per bucket) →
    the mean-equivalent parameter-shaped canonical form: every rank's
    residual feeds the Average reduction as ``r_k / world``, so the sum
    over ranks divided by ``world`` is the exact quantity whose effect on
    the reduced gradient must survive a rescale. On restore each of the
    M new ranks receives this mean — ``M * (mean / M) == mean`` — so the
    trajectory's pending error mass is preserved for any M."""
    if residual is None:
        return None
    mean_bufs = [
        b.reshape(world, -1).sum(axis=0) / world for b in residual.buffers
    ]
    return CanonicalResiduals(
        unpack(mean_bufs, spec),
        threshold=residual.threshold,
        block=residual.block,
    )


def _reshard_residuals(
    canonical: Optional[CanonicalResiduals],
    threshold_bytes: int,
    world: int,
) -> Optional[EFResiduals]:
    """Inverse of :func:`_canonicalize_residuals` for a world of
    ``world`` ranks: repack the mean-equivalent tree into the quantized
    bucket layout (padded to ``world * block``) and hand every rank the
    same buffer (``jnp.tile`` over the new world)."""
    if canonical is None:
        return None
    block = max(1, canonical.block)
    tree = canonical.tree
    buffers, _ = pack(
        tree, threshold_bytes, pad_multiple=world * block
    )
    return EFResiduals(
        [jnp.tile(b.astype(jnp.float32), world) for b in buffers],
        threshold=threshold_bytes,
        block=block,
    )


def unshard_opt_state(
    state: ShardedOptState, params, *, threshold_bytes: Optional[int] = None
) -> CanonicalOptState:
    """Flat-bucket sharded state (global view: full padded buffers) →
    world-size-portable canonical form (parameter-shaped leaves, padding
    stripped). The bucket layout comes from the state's own recorded
    ``threshold``/``world`` (``threshold_bytes`` overrides); ``params``
    must be the tree the state was built over (same structure, shapes,
    dtypes). Quantized states additionally canonicalize their EF
    residuals (see :func:`_canonicalize_residuals`)."""
    if threshold_bytes is None:
        threshold_bytes = int(state.threshold)
    world = int(state.world)
    # Quantized layouts pad to world*block; the block rides the state
    # (and, with EF on, the residual aux) so no env knob is consulted.
    # States from before the block field default to 1 (world-only pad).
    block = 1 if state.block is None else max(1, int(state.block))
    if state.residual is not None:
        block = max(block, state.residual.block or 1)
    spec = _pack_spec_for(params, threshold_bytes)
    # Exact expected sizes: payload rounded up to the recorded padding.
    expected = [s + (-s % (world * block)) for s in spec.bucket_sizes()]
    if state.residual is not None:
        got = [int(b.shape[0]) // world for b in state.residual.buffers]
        if got != expected:
            raise HorovodTpuError(
                f"EF residual buffers ({got} per rank) do not match the "
                f"padded bucket layout {expected} for world={world}, "
                f"block={block}"
            )

    def fix(n):
        if not _is_flat(n):
            return n
        if [int(b.shape[0]) for b in n.buffers] != expected:
            raise HorovodTpuError(
                "sharded opt-state buffers do not match the bucket layout "
                f"of these params (buffers "
                f"{[int(b.shape[0]) for b in n.buffers]} vs expected "
                f"{expected} for threshold={threshold_bytes}, "
                f"world={world}); pass the params and threshold_bytes the "
                "optimizer was built with"
            )
        return CanonicalBuckets(unpack(n.buffers, spec))

    return CanonicalOptState(
        inner=jax.tree.map(fix, state.inner, is_leaf=_is_flat),
        count=state.count,
        threshold=jnp.asarray(threshold_bytes, jnp.int32),
        block=jnp.asarray(block, jnp.int32),
        residual=_canonicalize_residuals(state.residual, spec, world),
    )


def reshard_opt_state(
    state: CanonicalOptState,
    params,
    *,
    world: Optional[int] = None,
    axis=None,
    threshold_bytes: Optional[int] = None,
) -> ShardedOptState:
    """Canonical checkpoint form → the flat-bucket layout for a world of
    ``world`` replicas (default: the current context's world size). The
    inverse of :func:`unshard_opt_state`, with the padding recomputed for
    the new world size — how a checkpoint saved at N devices restores
    onto M. ``params`` (the restore target's tree) is validated against
    the canonical leaves so a layout mismatch fails loudly instead of
    repacking garbage."""
    if world is None:
        world = _world_size(_norm_axes(axis))
    if threshold_bytes is None:
        threshold_bytes = int(state.threshold)
    p_struct = jax.tree.structure(params)
    # Quantized layout: the target world's padding is world*block. The
    # block rides the canonical state (and, with EF on, the residual
    # aux, which the structural restore takes from the TARGET).
    block = 1 if state.block is None else max(1, int(state.block))
    if state.residual is not None:
        block = max(block, state.residual.block or 1)
    pad_multiple = world * block

    def fix(n):
        if not _is_canonical(n):
            return n
        if jax.tree.structure(n.tree) != p_struct:
            raise HorovodTpuError(
                "canonical opt-state leaves do not match the target "
                "params tree (did the model change since the checkpoint "
                "was written?)"
            )
        buffers, _ = pack(n.tree, threshold_bytes, pad_multiple=pad_multiple)
        return FlatBuckets(buffers)

    return ShardedOptState(
        inner=jax.tree.map(fix, state.inner, is_leaf=_is_canonical),
        count=jnp.asarray(state.count, jnp.int32),
        threshold=jnp.asarray(threshold_bytes, jnp.int32),
        world=jnp.asarray(world, jnp.int32),
        block=jnp.asarray(block, jnp.int32),
        residual=_reshard_residuals(state.residual, threshold_bytes, world),
    )


def canonicalize_dist_state(
    state: DistributedOptState, params, *, world: Optional[int] = None
):
    """Quantized replicated state → world-size-portable canonical form:
    ``inner``/``acc`` are replicated and pass through; the EF residuals
    canonicalize to the mean-equivalent parameter-shaped tree. ``world``
    defaults to the live context's (canonicalization runs while the old
    world is still up — at checkpoint save / elastic snapshot)."""
    if state.residual is None:
        return state
    if world is None:
        world = _world_size(_norm_axes(None))
    threshold = state.residual.threshold or None
    spec = _pack_spec_for(params, threshold)
    return CanonicalDistOptState(
        inner=state.inner,
        acc=state.acc,
        count=state.count,
        residual=_canonicalize_residuals(state.residual, spec, world),
    )


def reshard_dist_state(
    state: CanonicalDistOptState, params, *, world: Optional[int] = None
) -> DistributedOptState:
    """Inverse of :func:`canonicalize_dist_state` for the current (or
    given) world size; threshold/block come from the canonical
    residuals' aux — which after a structural checkpoint restore is the
    TARGET optimizer's layout, so the repack always matches the live
    step."""
    if world is None:
        world = _world_size(_norm_axes(None))
    threshold = state.residual.threshold or None
    return DistributedOptState(
        inner=state.inner,
        acc=state.acc,
        count=jnp.asarray(state.count, jnp.int32),
        residual=_reshard_residuals(state.residual, threshold, world),
    )


def canonicalize_sharded_states(tree, params, **kwargs):
    """Replace every :class:`ShardedOptState` (and quantized
    :class:`DistributedOptState`) in ``tree`` with its canonical form
    (see :func:`unshard_opt_state` / :func:`canonicalize_dist_state`)."""

    def fix(n):
        if isinstance(n, ShardedOptState):
            return unshard_opt_state(n, params, **kwargs)
        if isinstance(n, DistributedOptState) and n.residual is not None:
            return canonicalize_dist_state(n, params)
        return n

    return jax.tree.map(
        fix,
        tree,
        is_leaf=lambda n: isinstance(
            n, (ShardedOptState, DistributedOptState)
        ),
    )


def reshard_sharded_states(tree, params, **kwargs):
    """Replace every canonical state in ``tree`` with the runtime form
    for the current world (see :func:`reshard_opt_state` /
    :func:`reshard_dist_state`)."""

    def fix(n):
        if isinstance(n, CanonicalOptState):
            return reshard_opt_state(n, params, **kwargs)
        if isinstance(n, CanonicalDistOptState):
            return reshard_dist_state(n, params)
        return n

    return jax.tree.map(
        fix,
        tree,
        is_leaf=lambda n: isinstance(
            n, (CanonicalOptState, CanonicalDistOptState)
        ),
    )


def grad(fun, argnums=0, *, op: ReduceOp = Average, axis=None, **allreduce_kwargs):
    """Like ``jax.grad`` but the returned gradients are allreduced.

    The JAX face of ``hvd.DistributedGradientTape``
    (``horovod/tensorflow/__init__.py:673``)."""

    def wrapped(*args, **kwargs):
        g = jax.grad(fun, argnums=argnums)(*args, **kwargs)
        return _reduce_grads(
            g, op, allreduce_kwargs.get("compression", Compression.none),
            allreduce_kwargs.get("prescale_factor", 1.0),
            allreduce_kwargs.get("postscale_factor", 1.0),
            axis, allreduce_kwargs.get("threshold_bytes"),
        )

    return wrapped


def value_and_grad(
    fun, argnums=0, *, has_aux=False, op: ReduceOp = Average, axis=None,
    average_loss: bool = True, **allreduce_kwargs,
):
    """Like ``jax.value_and_grad`` with allreduced gradients; the loss is
    also averaged across workers when ``average_loss`` (so every worker
    reports the global loss, matching ``MetricAverageCallback`` semantics,
    ``horovod/_keras/callbacks.py:48-87``)."""
    from .ops.collectives import allreduce as _allreduce

    def wrapped(*args, **kwargs):
        out, g = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux)(
            *args, **kwargs
        )
        g = _reduce_grads(
            g, op, allreduce_kwargs.get("compression", Compression.none),
            allreduce_kwargs.get("prescale_factor", 1.0),
            allreduce_kwargs.get("postscale_factor", 1.0),
            axis, allreduce_kwargs.get("threshold_bytes"),
        )
        if average_loss:
            if has_aux:
                loss, aux = out
                out = (_allreduce(loss, op=Average, axis=axis), aux)
            else:
                out = _allreduce(out, op=Average, axis=axis)
        return out, g

    return wrapped
