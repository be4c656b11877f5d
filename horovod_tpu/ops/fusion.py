"""Tensor fusion: pack many small tensors into few large collective calls.

TPU-native realization of the reference's fusion machinery — the
``FusionBufferManager`` (``horovod/common/fusion_buffer_manager.h:29-56``,
one persistent 128 MB buffer), ``Controller::FuseResponses``
(``controller.cc:777-914``, greedy fill up to the threshold with a
look-ahead that skips mixed dtypes), and the batched fusion-buffer
scatter/gather CUDA kernels (``ops/cuda/cuda_kernels.cu:45-123``).

On TPU none of that machinery needs to exist at runtime: one *variadic*
all-reduce per bucket (``lax.psum`` over a tuple of leaves emits a single
multi-operand all-reduce HLO) gives the one-launch-per-bucket behavior
with no staging buffer at all. An earlier revision packed buckets into
concatenated 1-D buffers first, assuming the copies would fuse away —
device traces showed they do not (~8 ms/step of concatenate +
dynamic-slice traffic on BERT-base). What survives from the reference
design is the *policy*: bucket greedily up to a byte threshold
(``HVDTPU_FUSION_THRESHOLD``, default 128 MB per the reference,
``operations.cc:444``) and never mix dtypes in a bucket — still useful on
TPU because each bucket maps to one collective launch on the ICI.
:func:`pack`/:func:`unpack` remain available for callers that want
physical fusion buffers (e.g. staging through host memory).
"""

from __future__ import annotations

import time as _time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..context import _axis_or_world as _norm_axes, _in_trace, _traced_size
from ..obs import registry as _obs
from ..utils import env as _env
from ..utils import timeline as _timeline
# Pad-aware packing/slot bookkeeping lives in ops/batching.py (shared
# verbatim with the serve dispatcher's request batching); re-exported
# here so every historical `fusion.pack` import keeps working.
from .batching import (  # noqa: F401
    PackSpec,
    _bucketize,
    _flatten,
    _Slot,
    leaf_nbytes,
    pack,
    unpack,
)
from .collectives import Average, ReduceOp, Sum, _axis_arg, _scale
from .compression import Compression, is_quantized
from .quantization import (
    SCALE_DTYPE,
    dequantize_blockwise,
    quantize_blockwise,
    quantized_wire_bytes,
)


# On a world of one nothing is exchanged: XLA drops the ``psum`` and the
# ``/ 1``, the gradient flows straight into the optimizer, and the
# compiler makes each weight's update the epilogue of the fusion that
# computes its gradient (p, m, v read and written in float32 per output
# tile of the dW matmul). For a LARGE weight that fusion runs far over
# the sum of its halves (PERF.md section 6, PR 52). Above one chip the
# all-reduce is the seam between the two; on one chip
# :func:`update_seams` keeps it for the matrices of at least this many
# elements that :func:`_kept_apart` names, and every other leaf (where
# fused wins, or where a seam was read and lost) passes as it is.
UPDATE_SEAM_MIN_SIZE = 2 ** 25


def _kept_apart(shape) -> bool:
    """Whether a gradient of this shape gets a seam: a matrix of at least
    :data:`UPDATE_SEAM_MIN_SIZE` elements whose sides lie within four of
    each other, which is an FFN's projection, up or down. A longer leaf
    is a lookup table or a vocabulary projection (7.4 to 65 to one in
    the cells here): a table's update stands apart already and the seam
    only makes its gradient's float32 copy, and three of the four cells
    read with such seams lost by them (PERF.md section 6, PR 52). A stack
    of experts (three dimensions) has not been read and keeps its
    program."""
    return (
        len(shape) == 2
        and shape[0] * shape[1] >= UPDATE_SEAM_MIN_SIZE
        and max(shape) <= 4 * min(shape)
    )


def _takes_param_along(shape) -> bool:
    """Whether a seamed leaf's barrier also takes the weight itself: an
    ``[in, out]`` matrix that widens. One that narrows (and a table
    ``[rows, width]`` this short) passes its gradient alone.

    This is a tuning to the compiler's plan, not a property of the
    update (PERF.md section 6, PR 52): a barrier also moves the TPU
    compiler's pick among its memory schedules for the whole step. With
    every gradient alone the Olmo-Hybrid step plans 3.7% over the form
    without seams (every update held to the program's end); with the
    weight beside the gradient of ``gate``, ``up`` and the head it plans
    2.5% under it, unless the table's weight rides too. The plan the rule
    is there for is pinned in ``tests/test_compile_plan.py``."""
    return shape[0] <= shape[1]


def record_update_seams(grads=()):
    """``fusion.update_seams`` (gradient leaves the trace now made keeps
    apart from their update) and ``fusion.update_seam_bytes`` (their
    bytes). Called with nothing by the paths that keep no seam, so the
    gauges never carry an earlier trace's count."""
    reg = _obs.always()
    reg.gauge("fusion.update_seams").set(len(grads))
    reg.gauge("fusion.update_seam_bytes").set(
        sum(leaf_nbytes(g) for g in grads)
    )


def update_seams(reduced, params, axis=None):
    """Keep the seam the exchange gives between a gradient and its update
    where nothing is exchanged. Returns ``(reduced, params)``.

    On a traced world of one each gradient leaf that :func:`_kept_apart`
    names (a matrix of at least :data:`UPDATE_SEAM_MIN_SIZE` elements,
    no longer than four times its width) goes through a
    ``lax.optimization_barrier`` of its own, so the compiler cannot make
    the update the epilogue of the fusion that computes the gradient.
    One barrier a leaf, never one over the tree, which would hold every
    gradient alive at once. Where :func:`_takes_param_along` says so the
    weight goes through the same barrier. Numerically the identity. Above
    one device, and outside a trace, the arguments come back as they are.
    """
    axes = _norm_axes(axis)
    grads, treedef = jax.tree.flatten(reduced)
    large = [
        i for i, g in enumerate(grads) if _kept_apart(g.shape)
    ] if _in_trace(axes) and _traced_size(axes) == 1 else []
    record_update_seams([grads[i] for i in large])
    if not large:
        return reduced, params
    weights = None if params is None else treedef.flatten_up_to(params)
    for i in large:
        if weights is not None and _takes_param_along(grads[i].shape):
            grads[i], weights[i] = lax.optimization_barrier(
                (grads[i], weights[i])
            )
        else:
            grads[i] = lax.optimization_barrier(grads[i])
    return (
        treedef.unflatten(grads),
        None if params is None else treedef.unflatten(weights),
    )


def _record_fusion_layout(kind: str, bucket_bytes, n_tensors, threshold):
    """Trace-time metrics for one fused collective: the compiled step
    will move exactly these bytes per call, so the gauges pin per-step
    collective traffic (the number ``tools/comm_audit.py`` predicts) and
    bucket count/fill without any runtime cost inside the jit."""
    if not _obs.enabled():
        return
    reg = _obs.metrics()
    total = int(sum(bucket_bytes))
    reg.counter("fusion.traces").inc()
    reg.gauge(f"fusion.{kind}.bytes_per_step").set(total)
    reg.gauge(f"fusion.{kind}.buckets").set(len(bucket_bytes))
    reg.gauge(f"fusion.{kind}.tensors").set(n_tensors)
    if bucket_bytes and threshold:
        reg.gauge(f"fusion.{kind}.bucket_fill").set(
            total / (len(bucket_bytes) * threshold)
        )


class FlatBuckets:
    """Pytree container marking "these leaves are fused flat buffers".

    The sharded optimizer threads its 1/N state through the inner optax
    transformation wrapped in this type, so downstream code (sharding
    specs, checkpoint canonicalization) can find the flat-bucket layout
    structurally — ``jax.tree.map(..., is_leaf=lambda x:
    isinstance(x, FlatBuckets))`` — no matter what state the inner
    optimizer builds around it.
    """

    def __init__(self, buffers: Sequence[jax.Array]):
        self.buffers = list(buffers)

    def __repr__(self):
        return f"FlatBuckets(n={len(self.buffers)})"


jax.tree_util.register_pytree_node(
    FlatBuckets,
    lambda fb: (tuple(fb.buffers), None),
    lambda aux, children: FlatBuckets(children),
)


class EFResiduals(FlatBuckets):
    """Per-bucket error-feedback residuals of the quantized collectives.

    One fp32 buffer per fused bucket holding THIS rank's accumulated
    quantization error — rank-local state, so the global (outside-
    ``shard_map``) view of each buffer is ``[world * padded]`` with dim 0
    sharded over the world axis (``sharded_state_specs`` maps any
    ``FlatBuckets`` subclass the same way). ``threshold``/``block`` ride
    as static aux data: the bucket-layout recipe the buffers were built
    for, read back by checkpoint canonicalization and elastic resharding
    instead of trusting the env knobs at restore time.
    """

    def __init__(self, buffers: Sequence[jax.Array], threshold: int = 0,
                 block: int = 0):
        super().__init__(buffers)
        self.threshold = int(threshold)
        self.block = int(block)

    def __repr__(self):
        return (
            f"EFResiduals(n={len(self.buffers)}, block={self.block})"
        )


jax.tree_util.register_pytree_node(
    EFResiduals,
    lambda r: (tuple(r.buffers), (r.threshold, r.block)),
    lambda aux, children: EFResiduals(children, *aux),
)


def bucket_byte_layout(
    tree, threshold_bytes: Optional[int] = None, *, pad_multiple: int = 1
) -> List[Tuple[str, int]]:
    """Predicted fused-bucket layout from shape/dtype metadata alone:
    ``[(dtype_name, padded_bytes), ...]`` per bucket, never materializing
    device data. ``tree`` may hold arrays or ``jax.ShapeDtypeStruct``
    leaves.

    The ONE static mirror of :func:`pack`/:func:`fused_allreduce`'s
    bucketing — same ``_bucketize`` walk, same ``pad_multiple`` rounding
    (pass the world size for the reduce-scatter layout) — used by the
    trace-time linter (:mod:`horovod_tpu.analysis`) and
    ``tools/comm_audit.py --lint`` to check a traced jaxpr against the
    policy's intent with zero subprocesses."""
    leaves, _, threshold_bytes = _flatten(tree, threshold_bytes)
    out: List[Tuple[str, int]] = []
    for bucket in _bucketize(leaves, threshold_bytes):
        size = sum(int(np.prod(leaf.shape)) for _, leaf in bucket)
        size += (-size) % max(1, pad_multiple)
        # Canonicalized like _bucketize's grouping key: the reported
        # dtype/itemsize must match what pack()'s jnp buffers (and the
        # traced collective groups) actually carry — e.g. numpy f64
        # leaves land on the wire as f32 under default x64-off.
        dt = np.dtype(jax.dtypes.canonicalize_dtype(bucket[0][1].dtype))
        out.append((dt.name, size * dt.itemsize))
    return out


def wire_buffer_bytes(
    tree,
    threshold_bytes: Optional[int] = None,
    *,
    world: int,
    sharded: bool = False,
    compression=Compression.none,
) -> dict:
    """Predicted per-device RESIDENT wire-buffer bytes from metadata
    alone — the memory-planner twin of :func:`bucket_byte_layout`'s
    wire-bytes accounting (that one prices what moves; this prices what
    *sits in HBM* while it moves).

    * replicated, unquantized: the variadic ``psum`` needs **zero**
      staging buffers (the whole point of the variadic design);
    * ``sharded=True``: :func:`pack` materializes every padded bucket as
      a flat per-device buffer before ``psum_scatter`` — those are real
      resident bytes;
    * quantized: the packed fp32 buckets plus the int8/fp8 payload and
      fp32 scale side-channel coexist around the all-to-all.

    Returns ``{"packed_bytes", "payload_bytes", "scale_bytes",
    "total_bytes"}`` — the analytic cross-check
    ``tools/hvdtpu_memplan.py`` prints next to the traced plan's wire
    category.
    """
    quant = is_quantized(compression)
    packed = payload = scales = 0
    if quant:
        for b in quantized_bucket_layout(
            tree, threshold_bytes, world=world, compression=compression
        ):
            packed += b["elements"] * 4  # fp32 packed bucket pre-quant
            payload += b["payload_bytes"]
            scales += b["scale_bytes"]
    elif sharded:
        packed = sum(
            b for _, b in bucket_byte_layout(
                tree, threshold_bytes, pad_multiple=world
            )
        )
    return {
        "packed_bytes": int(packed),
        "payload_bytes": int(payload),
        "scale_bytes": int(scales),
        "total_bytes": int(packed + payload + scales),
    }


def _chain_dispatch(wires: List[jax.Array], token):
    """Staggered dispatch: tie this bucket's collective operands to the
    previous bucket's reduction via ``lax.optimization_barrier``.

    Numerically the identity — the barrier only adds a scheduling edge.
    Without it XLA is free to issue the bucket collectives in any order
    (including last-packed first, which leaves the first-ready bucket
    waiting); with it the issue order is pinned to pack order, which
    :func:`_bucketize` arranges to be gradient-readiness order. Since
    collectives on one ICI ring execute serially anyway, the edge costs
    nothing on the wire; it just hands the latency-hiding scheduler a
    chain it can interleave backward compute into.
    """
    if token is None:
        return wires
    out = lax.optimization_barrier(tuple(wires) + (token,))
    return list(out[:-1])


def _uniform_cast_scale(leaves, a, world_factor: float):
    """Replica-uniform max-abs prescale for range-limited cast wires
    (fp16): one scalar over every floating leaf, ``pmax``'d across the
    axis so all ranks scale identically — a psum of per-rank-scaled
    values could never be unscaled. ``world_factor`` guards the SUM of
    the reduction (pass the world size), not just individual values;
    ``1`` for move-only legs (all-gather). Scale stays exactly 1 unless
    some |g| actually threatens the wire range, so ordinary steps are
    bit-identical to the legacy cast."""
    floats = [
        l for l in leaves if jnp.issubdtype(
            jax.dtypes.canonicalize_dtype(l.dtype), jnp.floating
        )
    ]
    if not floats:
        return None
    from .compression import FP16_SAFE_MAX

    gmax = jnp.max(
        jnp.stack([jnp.max(jnp.abs(l.astype(jnp.float32))) for l in floats])
    )
    gmax = lax.pmax(gmax, a)
    return jnp.maximum(1.0, world_factor * gmax / FP16_SAFE_MAX)


def _compress_wire(compression, x, scale):
    """Compress one wire value, passing the shared uniform scale to
    compressors that need it (see :func:`_uniform_cast_scale`)."""
    if scale is not None and getattr(compression, "needs_prescale", False):
        return compression.compress(x, scale=scale)
    return compression.compress(x)


def _record_quant_layout(kind: str, bucket_wire_bytes) -> None:
    """Trace-time quantized-wire gauges: the compiled step moves exactly
    these bytes per call (int8/fp8 payload + fp32 scales), the number
    ``tools/comm_audit.py --quant`` predicts."""
    if not _obs.enabled():
        return
    reg = _obs.metrics()
    reg.gauge(f"fusion.quant.{kind}.wire_bytes_per_step").set(
        int(sum(bucket_wire_bytes))
    )
    reg.gauge(f"fusion.quant.{kind}.buckets").set(len(bucket_wire_bytes))


def quantized_bucket_layout(
    tree,
    threshold_bytes: Optional[int] = None,
    *,
    world: int,
    compression,
) -> List[dict]:
    """Static quantized-wire prediction from metadata alone: per fused
    bucket, the padded element count (rounded to ``world * block`` so
    every all-to-all chunk is whole blocks) and the wire payload/scale
    bytes one quantized collective moves. The quant twin of
    :func:`bucket_byte_layout`, shared by the trace-time linter
    (``analysis/rules.py``) and ``tools/comm_audit.py --quant``."""
    block = compression.block_size()
    qspec = compression.spec
    pad_mult = world * block
    leaves, _, threshold_bytes = _flatten(tree, threshold_bytes)
    out = []
    for bucket in _bucketize(leaves, threshold_bytes):
        size = sum(int(np.prod(leaf.shape)) for _, leaf in bucket)
        size += (-size) % pad_mult
        out.append(
            {
                "wire_dtype": qspec.wire_dtype_name,
                "elements": size,
                "payload_bytes": size * qspec.itemsize,
                "scale_bytes": (size // block)
                * jnp.dtype(SCALE_DTYPE).itemsize,
                "wire_bytes": quantized_wire_bytes(size, block, qspec),
            }
        )
    return out


def _dequant_sum(q2, s2, world: int, block: int):
    """Sum the all-to-all result rows in fp32: ``q2 [world, chunk]``
    wire values, ``s2 [world, chunk/block]`` scales -> reduced ``[chunk]``
    fp32 (exact sum of the dequantized per-rank contributions — the
    local half of the quantized reduce-scatter)."""
    chunk = q2.shape[1]
    deq = q2.astype(jnp.float32).reshape(world, chunk // block, block)
    deq = deq * s2.astype(jnp.float32)[:, :, None]
    return deq.sum(axis=0).reshape(chunk)


def _quantized_reduce_shards(
    buffers,
    res_bufs,
    *,
    a,
    world: int,
    op: ReduceOp,
    prescale_factor: float,
    compression,
    stagger: bool,
):
    """Shared front half of the quantized allreduce/reduce-scatter: for
    each packed (``world*block``-padded) bucket, apply error feedback,
    quantize this rank's contribution blockwise, all-to-all the wire
    chunks, and dequantize-reduce locally. Returns
    ``(reduced fp32 shards, new residuals or None, stagger token)``.

    Error feedback (when ``res_bufs`` given): the residual added into the
    gradient BEFORE quantization is this rank's accumulated quantization
    error; the new residual is exactly the error of what was just sent —
    ``x - dequant(quant(x))`` — so no gradient mass is ever dropped, only
    delayed (Karimireddy et al., EF-SGD; the convergence-preserving half
    the wire format needs)."""
    qspec = compression.spec
    block = compression.block_size()
    shards = []
    new_res = []
    token = None
    for i, buf in enumerate(buffers):
        if not jnp.issubdtype(
            jax.dtypes.canonicalize_dtype(buf.dtype), jnp.floating
        ):
            raise ValueError(
                "quantized collectives support floating-point trees only; "
                f"got a {buf.dtype} bucket"
            )
        x = buf.astype(jnp.float32)
        x = _scale(x, prescale_factor)
        if res_bufs is not None:
            x = x + res_bufs[i].astype(jnp.float32)
        q, s = quantize_blockwise(x, block, qspec)
        if res_bufs is not None:
            new_res.append(x - dequantize_blockwise(q, s, block))
        if stagger:
            (q,) = _chain_dispatch([q], token)
        chunk = q.shape[0] // world
        q2 = lax.all_to_all(
            q.reshape(world, chunk), a, split_axis=0, concat_axis=0,
            tiled=True,
        )
        s2 = lax.all_to_all(
            s.reshape(world, -1), a, split_axis=0, concat_axis=0,
            tiled=True,
        )
        red = _dequant_sum(q2, s2, world, block)
        if stagger:
            token = red
        if op == Average:
            red = red / world
        shards.append(red)
    return shards, (new_res if res_bufs is not None else None), token


def _wrap_residuals(new_res, residuals, compression, threshold_bytes):
    if new_res is None:
        return None
    thr = getattr(residuals, "threshold", 0) or (threshold_bytes or 0)
    return EFResiduals(
        new_res, threshold=thr, block=compression.block_size()
    )


def quantized_fused_allreduce(
    tree,
    residuals=None,
    *,
    op: ReduceOp = Average,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    axis=None,
    threshold_bytes: Optional[int] = None,
    compression=Compression.int8,
    stagger: bool = False,
):
    """Allreduce a pytree on a blockwise-quantized wire with optional
    error feedback; returns ``(reduced_tree, new_residuals)``.

    The EQuARX-style transport expressed in framework collectives: a
    quantized ring allreduce is its reduce-scatter half plus its
    all-gather half, so the wire format is **all-to-all** of each rank's
    quantized chunks (ring cost ``(n-1)/n`` of the quantized payload),
    a local fp32 dequantize-reduce, then **all-gather** of the
    requantized reduced shards (another ``(n-1)/n``) — total exactly one
    ring allreduce at wire width ``itemsize + 4/block`` bytes/element,
    ~2x below bf16 at int8. Per-block max-abs scales ride as an fp32
    side channel; ``residuals`` (an :class:`EFResiduals`, one fp32
    buffer per bucket) arms error feedback on this rank's send-side
    quantization. The second (broadcast) quantization error is common to
    all ranks and unbiased across steps; it gets no residual.
    """
    axes = _norm_axes(axis)
    if op not in (Average, Sum):
        raise ValueError("quantized_fused_allreduce supports Average/Sum")
    if not _in_trace(axes):
        from .collectives import _require_axes_bound

        _require_axes_bound(axes, "quantized_fused_allreduce")
    a = _axis_arg(axes)
    world = _traced_size(axes)
    block = compression.block_size()
    mx = _obs.enabled()
    t0 = _time.perf_counter() if mx else 0.0
    buffers, spec = pack(
        tree, threshold_bytes, pad_multiple=world * block
    )
    res_bufs = residuals.buffers if isinstance(residuals, FlatBuckets) else (
        list(residuals) if residuals is not None else None
    )
    if res_bufs is not None and len(res_bufs) != len(buffers):
        raise ValueError(
            f"residuals carry {len(res_bufs)} buckets for a "
            f"{len(buffers)}-bucket layout; pass the residual state the "
            "optimizer built for these params"
        )
    shards, new_res, token = _quantized_reduce_shards(
        buffers,
        res_bufs,
        a=a,
        world=world,
        op=op,
        prescale_factor=prescale_factor,
        compression=compression,
        stagger=stagger,
    )
    qspec = compression.spec
    out_bufs = []
    for buf, red in zip(buffers, shards):
        rq, rs = quantize_blockwise(red, block, qspec)
        if stagger:
            (rq,) = _chain_dispatch([rq], token)
        fq = lax.all_gather(rq, a, axis=0, tiled=True)
        fs = lax.all_gather(rs, a, axis=0, tiled=True)
        if stagger:
            token = fq
        out = dequantize_blockwise(fq, fs, block)
        out_bufs.append(_scale(out, postscale_factor).astype(buf.dtype))
    if mx:
        # One ring allreduce equivalent per bucket: a2a + ag both move
        # the quantized bucket once.
        per_bucket = [
            2 * quantized_wire_bytes(int(b.shape[0]), block, qspec)
            for b in buffers
        ]
        _record_quant_layout("allreduce", per_bucket)
        _obs.metrics().histogram("fusion.quant_ms").observe(
            (_time.perf_counter() - t0) * 1e3
        )
    return (
        unpack(out_bufs, spec),
        _wrap_residuals(new_res, residuals, compression, threshold_bytes),
    )


def quantized_fused_reducescatter(
    tree,
    residuals=None,
    *,
    op: ReduceOp = Average,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    axis=None,
    threshold_bytes: Optional[int] = None,
    compression=Compression.int8,
    stagger: bool = False,
):
    """Reduce-scatter a pytree on the quantized wire: the all-to-all +
    local-dequantize-reduce front half of :func:`quantized_fused_
    allreduce` — each replica ends with the fp32-accurate reduced 1/N
    shard of every bucket (padded to ``world * block`` so chunks are
    whole blocks). Returns ``(FlatBuckets shards, PackSpec, new
    residuals)``; shards come back in the input dtype, ready for the
    sharded optimizer update, and the matching update all-gather reuses
    the same wire via ``fused_allgather(compression=Compression.int8)``.
    """
    axes = _norm_axes(axis)
    if op not in (Average, Sum):
        raise ValueError("quantized_fused_reducescatter supports Average/Sum")
    if not _in_trace(axes):
        from .collectives import _require_axes_bound

        _require_axes_bound(axes, "quantized_fused_reducescatter")
    a = _axis_arg(axes)
    world = _traced_size(axes)
    block = compression.block_size()
    qspec = compression.spec
    mx = _obs.enabled()
    t0 = _time.perf_counter() if mx else 0.0
    buffers, spec = pack(
        tree, threshold_bytes, pad_multiple=world * block
    )
    res_bufs = residuals.buffers if isinstance(residuals, FlatBuckets) else (
        list(residuals) if residuals is not None else None
    )
    shards, new_res, _ = _quantized_reduce_shards(
        buffers,
        res_bufs,
        a=a,
        world=world,
        op=op,
        prescale_factor=prescale_factor,
        compression=compression,
        stagger=stagger,
    )
    out = [
        _scale(red, postscale_factor).astype(buf.dtype)
        for buf, red in zip(buffers, shards)
    ]
    if mx:
        per_bucket = [
            quantized_wire_bytes(int(b.shape[0]), block, qspec)
            for b in buffers
        ]
        _record_quant_layout("reducescatter", per_bucket)
        _obs.metrics().histogram("fusion.quant_ms").observe(
            (_time.perf_counter() - t0) * 1e3
        )
    return (
        FlatBuckets(out),
        spec,
        _wrap_residuals(new_res, residuals, compression, threshold_bytes),
    )


def fused_allreduce(
    tree,
    *,
    op: ReduceOp = Average,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    axis=None,
    threshold_bytes: Optional[int] = None,
    compression=Compression.none,
    stagger: bool = False,
):
    """Allreduce an entire pytree of tensors with bucketed fusion.

    The workhorse behind ``DistributedOptimizer``: the analog of the
    reference's negotiate→fuse→single-collective cycle
    (``controller.cc:777-914`` + ``MEMCPY_IN_FUSION_BUFFER`` activities),
    compiled to one ``psum`` per ≤threshold bucket. ``compression`` casts
    the wire buffers (fp16/bf16) like the reference's
    ``Compression.fp16`` path. ``stagger`` chains the bucket collectives
    in pack order (see :func:`_chain_dispatch`) for the overlap pipeline.
    """
    axes = _norm_axes(axis)
    if op not in (Average, Sum):
        raise ValueError("fused_allreduce supports Average/Sum; use allreduce()")
    if not _in_trace(axes):
        from .collectives import _is_traced, _require_axes_bound

        if any(_is_traced(l) for l in jax.tree.leaves(tree)):
            # Traced values but axes unbound (plain jit without shard_map):
            # raise the actionable error, not a numpy conversion failure.
            _require_axes_bound(axes, "fused_allreduce")
        # Concrete arrays outside shard_map: process-level path (DCN).
        # Wire quantization is an SPMD feature; the eager path moves
        # uncompressed bytes.
        from . import eager as _eager

        leaves, treedef = jax.tree.flatten(tree)
        out = [
            _eager.allreduce(l, op, prescale_factor, postscale_factor)
            for l in leaves
        ]
        return jax.tree.unflatten(treedef, out)
    if is_quantized(compression):
        out, _ = quantized_fused_allreduce(
            tree,
            None,
            op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            axis=axis,
            threshold_bytes=threshold_bytes,
            compression=compression,
            stagger=stagger,
        )
        return out
    a = _axis_arg(axes)
    world = _traced_size(axes)

    # TPU-native fusion: one VARIADIC all-reduce per bucket (``lax.psum``
    # over a tuple emits a single multi-operand all-reduce HLO).  The
    # reference must physically memcpy tensors into a fusion buffer for
    # NCCL (``cuda_kernels.cu:45-123``); on TPU that explicit pack/unpack
    # compiles to real concatenate + dynamic-slice traffic — measured
    # ~8 ms/step on BERT-base (132 MB of fp32 gradients copied twice) —
    # while the variadic collective gives the same one-launch-per-bucket
    # behavior with zero staging copies.
    leaves, treedef, threshold_bytes = _flatten(tree, threshold_bytes)
    buckets = _bucketize(leaves, threshold_bytes)
    tl = _timeline.global_timeline()
    if tl.enabled or _obs.enabled():
        # Trace-time record of the fusion layout (the SPMD analog of the
        # reference's per-cycle fusion events): how many tensors were
        # packed into how many buckets of what size.
        bucket_bytes = [
            sum(leaf_nbytes(leaf) for _, leaf in bucket)
            for bucket in buckets
        ]
        _record_fusion_layout(
            "allreduce", bucket_bytes, len(leaves), threshold_bytes
        )
        if tl.enabled:
            tl.instant(
                "fusion",
                "FUSE_BUCKETS",
                {
                    "n_tensors": len(leaves),
                    "n_buckets": len(buckets),
                    "bucket_bytes": bucket_bytes,
                },
            )
    wire_scale = None
    if getattr(compression, "needs_prescale", False):
        wire_scale = _uniform_cast_scale(leaves, a, float(world))
    out_leaves: List[Optional[jax.Array]] = [None] * len(leaves)
    token = None
    for bucket in buckets:
        wires, cctxs = [], []
        for _, leaf in bucket:
            wire, cctx = _compress_wire(
                compression, _scale(leaf, prescale_factor), wire_scale
            )
            wires.append(wire)
            cctxs.append(cctx)
        if stagger:
            wires = _chain_dispatch(wires, token)
        reds = lax.psum(tuple(wires), a)
        if stagger:
            token = reds[0]
        for (i, _), red, cctx in zip(bucket, reds, cctxs):
            red = compression.decompress(red, cctx)
            if op == Average:
                if jnp.issubdtype(red.dtype, jnp.integer):
                    red = red // world
                else:
                    red = red / world
            out_leaves[i] = _scale(red, postscale_factor)
    if treedef is None:
        return out_leaves
    return jax.tree.unflatten(treedef, out_leaves)


def fused_reducescatter(
    tree,
    *,
    op: ReduceOp = Average,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    axis=None,
    threshold_bytes: Optional[int] = None,
    compression=Compression.none,
    stagger: bool = False,
) -> Tuple[FlatBuckets, PackSpec]:
    """Reduce-scatter a pytree with bucketed fusion: each replica keeps a
    contiguous 1/N shard of every fused bucket.

    The front half of the sharded (ZeRO-1) optimizer update
    (arXiv:2004.13336): instead of the variadic psum handing every
    replica the full reduction, buckets are *physically* packed (here the
    copies buy something — the flat layout IS the shard layout the
    optimizer state lives in), padded to a multiple of the world size
    (``PackSpec.pad``), and ``psum_scatter`` hands replica ``k`` elements
    ``[k*S/N, (k+1)*S/N)`` of each bucket. Wire bytes equal one ring
    allreduce's reduce-scatter half; the matching :func:`fused_allgather`
    completes allreduce byte parity.

    Returns ``(shards, spec)``: ``shards`` is a :class:`FlatBuckets` of
    per-bucket shard buffers (size ``padded/N``), ``spec`` the recipe to
    restore the original tree after :func:`fused_allgather`.
    """
    axes = _norm_axes(axis)
    if op not in (Average, Sum):
        raise ValueError("fused_reducescatter supports Average/Sum")
    if not _in_trace(axes):
        from .collectives import _require_axes_bound

        _require_axes_bound(axes, "fused_reducescatter")
    if is_quantized(compression):
        shards, spec, _ = quantized_fused_reducescatter(
            tree,
            None,
            op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            axis=axis,
            threshold_bytes=threshold_bytes,
            compression=compression,
            stagger=stagger,
        )
        return shards, spec
    a = _axis_arg(axes)
    world = _traced_size(axes)
    buffers, spec = pack(tree, threshold_bytes, pad_multiple=world)
    tl = _timeline.global_timeline()
    if tl.enabled or _obs.enabled():
        bucket_bytes = [int(b.size) * b.dtype.itemsize for b in buffers]
        _record_fusion_layout(
            "reducescatter",
            bucket_bytes,
            spec.n_leaves,
            threshold_bytes or _env.fusion_threshold_bytes(),
        )
        if tl.enabled:
            tl.instant(
                "fusion",
                "FUSE_BUCKETS",
                {
                    "mode": "reducescatter",
                    "n_tensors": spec.n_leaves,
                    "n_buckets": len(buffers),
                    "bucket_bytes": bucket_bytes,
                    "pad_elements": list(spec.pad),
                },
            )
    wire_scale = None
    if getattr(compression, "needs_prescale", False):
        wire_scale = _uniform_cast_scale(buffers, a, float(world))
    shards = []
    token = None
    for buf in buffers:
        wire, cctx = _compress_wire(
            compression, _scale(buf, prescale_factor), wire_scale
        )
        if stagger:
            (wire,) = _chain_dispatch([wire], token)
        red = lax.psum_scatter(wire, a, scatter_dimension=0, tiled=True)
        if stagger:
            token = red
        red = compression.decompress(red, cctx)
        if op == Average:
            if jnp.issubdtype(red.dtype, jnp.integer):
                red = red // world
            else:
                red = red / world
        shards.append(_scale(red, postscale_factor))
    return FlatBuckets(shards), spec


def fused_allgather(
    shards,
    spec: PackSpec,
    *,
    axis=None,
    compression=Compression.none,
    stagger: bool = False,
):
    """All-gather per-bucket shards back into the original pytree.

    The back half of the sharded optimizer update: after the inner
    transformation ran on the local 1/N shard, gather every replica's
    shard (optionally compressed on the wire — the EQuARX-style
    low-precision transport leg, arXiv:2506.17615), strip the packing pad
    and restore the original tree via ``spec``.
    """
    axes = _norm_axes(axis)
    if not _in_trace(axes):
        from .collectives import _require_axes_bound

        _require_axes_bound(axes, "fused_allgather")
    a = _axis_arg(axes)
    buffers = shards.buffers if isinstance(shards, FlatBuckets) else list(shards)
    if _obs.enabled():
        # Payload convention matches the reduce-scatter leg: the FULL
        # padded bucket (the gathered result), not the 1/N shard sent —
        # so RS + AG gauges sum to ring-allreduce parity the way
        # ``tools/comm_audit.py --parity`` accounts it.
        _record_fusion_layout(
            "allgather",
            [
                int(n) * buf.dtype.itemsize
                for n, buf in zip(spec.padded_sizes(), buffers)
            ],
            spec.n_leaves,
            _env.fusion_threshold_bytes(),
        )
    if is_quantized(compression):
        return _quantized_gather_unpack(
            buffers, spec, a, compression, stagger
        )
    wire_scale = None
    if getattr(compression, "needs_prescale", False):
        # Move-only leg: the gathered wire holds OTHER ranks' values, so
        # the scale undone at decompress must be the same everywhere —
        # pmax'd, with no world factor (nothing is summed).
        wire_scale = _uniform_cast_scale(buffers, a, 1.0)
    full = []
    token = None
    for buf in buffers:
        wire, cctx = _compress_wire(compression, buf, wire_scale)
        if stagger:
            (wire,) = _chain_dispatch([wire], token)
        gathered = lax.all_gather(wire, a, axis=0, tiled=True)
        if stagger:
            token = gathered
        full.append(compression.decompress(gathered, cctx))
    return unpack(full, spec)


def _quantized_gather_unpack(buffers, spec, a, compression, stagger):
    """All-gather per-bucket shards on the quantized wire: each rank
    quantizes its shard blockwise, int8/fp8 payload + fp32 scales ride
    the all-gather, and every rank dequantizes the full bucket. Shards
    whose length is not a block multiple are padded per rank and the
    interleaved pads stripped after the gather, so this leg composes with
    a non-quantized reduce-scatter too (``gather_compression=int8``)."""
    mx = _obs.enabled()
    t0 = _time.perf_counter() if mx else 0.0
    block = compression.block_size()
    qspec = compression.spec
    full = []
    wire_bytes = []
    token = None
    for buf in buffers:
        shard = int(buf.shape[0])
        pad = (-shard) % block
        x = buf.astype(jnp.float32)
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
        q, s = quantize_blockwise(x, block, qspec)
        if stagger:
            (q,) = _chain_dispatch([q], token)
        fq = lax.all_gather(q, a, axis=0, tiled=True)
        fs = lax.all_gather(s, a, axis=0, tiled=True)
        if stagger:
            token = fq
        out = dequantize_blockwise(fq, fs, block)
        if pad:
            world = fq.shape[0] // (shard + pad)
            out = out.reshape(world, shard + pad)[:, :shard].reshape(-1)
        # Gauge convention matches the unquantized leg: the FULL gathered
        # payload (what lands on every rank), here in wire bytes.
        wire_bytes.append(
            int(fq.shape[0]) * qspec.itemsize
            + int(fs.shape[0]) * jnp.dtype(SCALE_DTYPE).itemsize
        )
        full.append(out.astype(buf.dtype))
    if mx:
        _record_quant_layout("allgather", wire_bytes)
        _obs.metrics().histogram("fusion.quant_ms").observe(
            (_time.perf_counter() - t0) * 1e3
        )
    return unpack(full, spec)


def shard_slice(buffers, axis=None) -> FlatBuckets:
    """Each replica's contiguous 1/N slice of full fused buffers — the
    layout ``psum_scatter`` produces, taken locally (used to shard the
    replicated params for the 1/N optimizer update)."""
    axes = _norm_axes(axis)
    a = _axis_arg(axes)
    world = _traced_size(axes)
    idx = lax.axis_index(a)
    bufs = buffers.buffers if isinstance(buffers, FlatBuckets) else list(buffers)
    out = []
    for buf in bufs:
        n = buf.shape[0] // world
        out.append(lax.dynamic_slice_in_dim(buf, idx * n, n))
    return FlatBuckets(out)
