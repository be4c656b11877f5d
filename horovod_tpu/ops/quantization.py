"""Blockwise-scaled quantization: the int8/fp8 wire format.

The EQuARX-style transport (arXiv:2506.17615) realized as framework-level
wire codecs: a flat buffer is split into fixed-size blocks, each block is
scaled by its own max-abs so the full dynamic range of the wire dtype is
used per block, and the per-block scales ride along as a small fp32
side-channel (``4/block`` overhead — ~1.6% at the default block of 256).
:mod:`horovod_tpu.ops.fusion` fuses these codecs into ``pack``/``unpack``
around quantized collectives; :mod:`horovod_tpu.ops.compression` exposes
them as ``Compression.int8`` / ``Compression.fp8``.

Two implementations with identical numerics:

* pure-jax (:func:`quantize_blockwise` with ``impl="jax"``) — the
  portable fallback, used on CPU and whenever the Pallas constraints
  don't hold;
* Pallas TPU kernels (``ops/pallas_kernels.py``:
  ``quantize_blockwise_pallas`` / ``dequantize_blockwise_pallas``) —
  one VMEM pass per tile computing scale+round+cast in place, selected
  automatically on TPU for int8 with 128-aligned blocks. The fast-tier
  CPU-interpreter parity test (``tests/test_quantization.py``) pins the
  two implementations to each other bit-for-bit.

Error feedback lives one layer up (``optimizer.py``): the quantization
error of each rank's *sent* gradient is kept as a per-bucket residual and
added back into the next step's gradient, which removes the rounding bias
that otherwise stalls convergence at aggressive block sizes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..context import device_platform
from ..utils import env as _env

__all__ = [
    "QuantSpec",
    "INT8",
    "FP8",
    "quant_spec",
    "quantize_blockwise",
    "dequantize_blockwise",
    "quantized_wire_bytes",
    "SCALE_DTYPE",
    "QuantizedWeight",
    "quantize_weight",
    "dequantize_weight",
    "quantize_params",
    "int8_weight_matmul",
    "qmatmul",
    "quantize_kv_heads",
    "dequantize_kv_heads",
    "E4M3_MAX",
    "E5M2_MAX",
    "fp8_scale_from_history",
    "fp8_push_amax",
    "fp8_saturating_cast",
    "fp8_matmul",
]

SCALE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """One wire format: dtype, the max representable magnitude the block
    scale normalizes to, and whether values need integer rounding."""

    name: str
    wire_dtype_name: str
    qmax: float
    integer: bool

    @property
    def wire_dtype(self):
        return jnp.dtype(self.wire_dtype_name)

    @property
    def itemsize(self) -> int:
        return self.wire_dtype.itemsize


INT8 = QuantSpec(name="int8", wire_dtype_name="int8", qmax=127.0, integer=True)
# e4m3 keeps the most mantissa of the fp8 pair; 448 is its max finite.
FP8 = QuantSpec(
    name="fp8", wire_dtype_name="float8_e4m3fn", qmax=448.0, integer=False
)


def quant_spec(name: str) -> QuantSpec:
    if name == "int8":
        return INT8
    if name == "fp8":
        return FP8
    raise ValueError(f"unknown quantization {name!r}; use int8|fp8")


def default_block() -> int:
    return _env.quant_block()


def quantized_wire_bytes(n_elements: int, block: int, spec: QuantSpec) -> int:
    """Wire bytes for one quantized buffer: payload in the wire dtype
    plus the fp32 per-block scales. The ONE sizing rule shared by the
    fusion gauges, the linter's quant parity prediction and
    ``tools/comm_audit.py --quant``."""
    n_blocks = -(-n_elements // block)
    return n_elements * spec.itemsize + n_blocks * jnp.dtype(
        SCALE_DTYPE
    ).itemsize


def _blocks_view(x: jax.Array, block: int) -> Tuple[jax.Array, int, int]:
    """Flat buffer -> ([n_blocks, block] fp32 view, n, pad). Arbitrary
    lengths are zero-padded up to a whole block (padding quantizes to
    exact zeros and is sliced off after dequantization)."""
    n = int(x.shape[0])
    pad = (-n) % block
    xf = x.astype(jnp.float32)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad,), jnp.float32)])
    return xf.reshape(-1, block), n, pad


def _quantize_rows_jax(
    rows: jax.Array, spec: QuantSpec
) -> Tuple[jax.Array, jax.Array]:
    """[n_blocks, block] fp32 -> (wire rows, [n_blocks] fp32 scales).

    Scale maps each block's max-abs onto ``qmax``; all-zero blocks get
    scale 1 (quantize to exact zeros, divide never sees 0)."""
    amax = jnp.max(jnp.abs(rows), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / spec.qmax, 1.0)
    y = rows / scale
    if spec.integer:
        q = jnp.clip(jnp.round(y), -spec.qmax, spec.qmax).astype(
            spec.wire_dtype
        )
    else:
        q = y.astype(spec.wire_dtype)
    return q, scale[:, 0].astype(SCALE_DTYPE)


def _use_pallas(spec: QuantSpec, block: int) -> bool:
    # The TPU kernel is int8-only (Mosaic fp8 cast support varies by
    # generation) and wants 128-aligned lanes; everything else takes the
    # pure-jax path, which XLA fuses well.
    return (
        spec.integer
        and block % 128 == 0
        and device_platform() == "tpu"
    )


def quantize_blockwise(
    x: jax.Array,
    block: Optional[int] = None,
    spec: QuantSpec = INT8,
    *,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Quantize a flat buffer: returns ``(q, scales)`` with ``q`` the
    wire-dtype payload (same length as ``x``) and ``scales`` fp32 of
    length ``ceil(len/block)``. ``impl`` forces the ``"jax"``/
    ``"pallas"`` implementation (default: auto — Pallas on TPU for
    128-aligned int8 blocks); execution mode stays automatic either way
    (compiled on TPU, Pallas interpreter elsewhere)."""
    if block is None:
        block = default_block()
    rows, n, pad = _blocks_view(x, block)
    use_pallas = (
        impl == "pallas" if impl else _use_pallas(spec, block)
    )
    if use_pallas:
        from .pallas_kernels import quantize_blockwise_pallas

        # interpret resolves inside the kernel helper (auto: compiled on
        # TPU, interpreter elsewhere) — forcing impl="pallas" picks the
        # implementation, never the execution mode.
        q_rows, scales = quantize_blockwise_pallas(
            rows, qmax=spec.qmax, wire_dtype=spec.wire_dtype,
            integer=spec.integer,
        )
    else:
        q_rows, scales = _quantize_rows_jax(rows, spec)
    q = q_rows.reshape(-1)
    if pad:
        q = q[:n]
    return q, scales


# -- int8 serving weights -------------------------------------------------
#
# The serving-plane face of the same codec: a 2-D matmul weight is
# quantized ONCE (at ServePool checkpoint load) with one scale per output
# channel — exactly blockwise quantization of the column-major flat view
# with block = K, so the wire codec above is reused verbatim — and the
# matmul applies the scales in-kernel (ops/pallas_kernels.int8_matmul_pallas
# on TPU; the blocked pure-jax twin below elsewhere). Weights live in HBM
# as int8: half the bytes of bf16, and serving matmuls at small batch are
# weight-bandwidth-bound, so the byte cut is the throughput win (EQuARX's
# argument, arXiv:2506.17615, applied to the compute path instead of the
# wire).


class QuantizedWeight:
    """One quantized matmul weight: ``q`` int8 ``[K, N]`` + ``scales``
    fp32 ``[N]`` (per output channel). A pytree node, so quantized param
    trees flow through ``jax.jit``/``tree.map`` unchanged; ``dtype_name``
    (static aux) records the original storage dtype for
    :func:`dequantize_weight`."""

    def __init__(self, q, scales, dtype_name: str = "float32"):
        self.q = q
        self.scales = scales
        self.dtype_name = dtype_name

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def __repr__(self):
        return (
            f"QuantizedWeight(shape={tuple(self.q.shape)}, "
            f"dtype={self.dtype_name})"
        )


jax.tree_util.register_pytree_node(
    QuantizedWeight,
    lambda w: ((w.q, w.scales), w.dtype_name),
    lambda aux, children: QuantizedWeight(*children, dtype_name=aux),
)


def quantize_weight(w: jax.Array, spec: QuantSpec = INT8) -> QuantizedWeight:
    """Quantize a ``[K, N]`` matmul weight with per-output-channel scales.

    Reuses :func:`quantize_blockwise` on the column-major flat view with
    ``block = K`` — one block per output column, so each column's full
    dynamic range maps onto the wire dtype and the scale vector is
    exactly the codec's per-block scales."""
    if w.ndim != 2:
        raise ValueError(f"quantize_weight needs a 2-D weight, got {w.shape}")
    k, n = w.shape
    q_flat, scales = quantize_blockwise(
        w.T.reshape(-1), block=k, spec=spec, impl="jax"
    )
    return QuantizedWeight(
        q_flat.reshape(n, k).T, scales, dtype_name=np.dtype(w.dtype).name
    )


def dequantize_weight(w: QuantizedWeight) -> jax.Array:
    """Exact inverse transport (up to the wire rounding) back to the
    original storage dtype."""
    return (
        w.q.astype(jnp.float32) * w.scales.reshape(1, -1)
    ).astype(jnp.dtype(w.dtype_name))


def quantize_params(tree, spec: QuantSpec = INT8, *, min_size: int = 4096):
    """Replace every 2-D floating leaf of at least ``min_size`` elements
    with a :class:`QuantizedWeight` (what ``ServePool(weight_dtype='int8')``
    does once per checkpoint load). Biases, norms, embeddings-as-vectors
    and tiny heads stay in their original dtype — the byte win is in the
    big matmul weights and small tensors only add rounding."""

    def fix(leaf):
        if (
            getattr(leaf, "ndim", 0) == 2
            and jnp.issubdtype(
                jax.dtypes.canonicalize_dtype(leaf.dtype), jnp.floating
            )
            and int(np.prod(leaf.shape)) >= min_size
        ):
            return quantize_weight(jnp.asarray(leaf), spec)
        return leaf

    return jax.tree.map(fix, tree)


_MATMUL_BLOCK_K = 256  # K-tile of the blocked accumulation (both impls)


def int8_weight_matmul(
    x: jax.Array,
    w: QuantizedWeight,
    *,
    impl: Optional[str] = None,
    block_k: int = _MATMUL_BLOCK_K,
) -> jax.Array:
    """``x @ w`` with the scales applied in-kernel: fp32 accumulation
    over ``block_k`` K-tiles, per-column scale at finalize, result cast
    to ``x.dtype``. ``impl`` forces ``"jax"``/``"pallas"`` (default:
    Pallas on TPU, the blocked pure-jax twin elsewhere — IDENTICAL
    accumulation order, pinned bit-for-bit by the fast-tier parity
    test)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    if k != w.q.shape[0]:
        raise ValueError(
            f"matmul shapes disagree: x {x.shape} vs weight {w.q.shape}"
        )
    x2 = x.reshape(-1, k)
    use_pallas = (
        impl == "pallas" if impl else device_platform() == "tpu"
    )
    if use_pallas:
        from .pallas_kernels import int8_matmul_pallas

        out = int8_matmul_pallas(x2, w.q, w.scales, block_k=block_k)
    else:
        m, n = x2.shape[0], w.q.shape[1]
        # Padding mirrors the Pallas grid exactly (tile clamp, then round
        # up, on every dim) so each partial dot has the identical padded
        # shape — the reduction tree, and therefore the fp32 rounding,
        # matches the kernel bit-for-bit (tiny unpadded shapes would
        # otherwise take XLA's gemv path with a different K order).
        ru = lambda a, b: -(-a // b) * b  # noqa: E731
        bk = min(block_k, ru(k, 128))
        m_pad, n_pad, k_pad = ru(m, 8), ru(n, 128), ru(k, bk)
        xp = jnp.pad(x2, ((0, m_pad - m), (0, k_pad - k)))
        wq = jnp.pad(w.q, ((0, k_pad - k), (0, n_pad - n)))
        acc = jnp.zeros((m_pad, n_pad), jnp.float32)
        for k0 in range(0, k_pad, bk):
            acc = acc + jax.lax.dot_general(
                xp[:, k0:k0 + bk],
                wq[k0:k0 + bk].astype(x2.dtype),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        out = (
            acc[:m, :n] * w.scales.reshape(1, -1)
        ).astype(x.dtype)
    return out.reshape(*lead, w.q.shape[1])


def qmatmul(x: jax.Array, w) -> jax.Array:
    """Quantization-transparent matmul: ``w`` may be a plain array
    (falls through to ``x @ w``) or a :class:`QuantizedWeight` (runs the
    int8 path). Serving ``infer_fn``s written against this one call work
    under any ``ServePool(weight_dtype=...)``."""
    if isinstance(w, QuantizedWeight):
        return int8_weight_matmul(x, w)
    return x @ w


# -- int8 KV-cache storage -------------------------------------------------
#
# The serving plane's third face of the codec: the paged KV-cache pool
# (serve/kvcache.py) stores keys/values int8 with one fp32 max-abs scale
# per (token, head) — blockwise quantization with block = head_dim, the
# natural block for attention (each head's vector is scaled as one unit,
# so a loud head cannot crush a quiet one's resolution). Scales ride in a
# parallel fp32 pool: 4/head_dim overhead (~6% at head_dim 64), against
# a 4x HBM cut for fp32 caches (2x vs bf16) — KV capacity is what bounds
# decode batch width, so the byte cut is admission headroom.


def quantize_kv_heads(
    x: jax.Array, spec: QuantSpec = INT8
) -> Tuple[jax.Array, jax.Array]:
    """Quantize per-head vectors: ``x[..., H, head_dim]`` → ``(q, scales)``
    with ``q`` the wire-dtype payload (same shape) and ``scales`` fp32 of
    shape ``x.shape[:-1]`` (one scale per head vector)."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scales = jnp.where(amax > 0, amax / spec.qmax, 1.0).astype(SCALE_DTYPE)
    y = x.astype(jnp.float32) / scales[..., None]
    if spec.integer:
        y = jnp.round(y)
    q = jnp.clip(y, -spec.qmax, spec.qmax).astype(spec.wire_dtype)
    return q, scales


def dequantize_kv_heads(
    q: jax.Array, scales: jax.Array, out_dtype=jnp.float32
) -> jax.Array:
    """Inverse of :func:`quantize_kv_heads` (up to wire rounding)."""
    return (
        q.astype(jnp.float32) * scales[..., None].astype(jnp.float32)
    ).astype(out_dtype)


# -- fp8 training compute ---------------------------------------------------
#
# The fourth face of the codec (HVDTPU_COMPUTE_DTYPE=fp8): training
# matmuls run on e4m3 operands (e5m2 for the incoming gradient in
# backward) under per-tensor *delayed* scales — each tensor's scale is
# derived from a short ring of past max-abs values, so the cast is
# host-free and in-graph (no data-dependent rescale stalls the step).
# The helpers below are the scale algebra; the module-level wiring
# (amax state as TrainState params, fp32 master weights, the EF cast
# residual) lives in ops/fp8.py.

E4M3_MAX = 448.0  # max finite of float8_e4m3fn
E5M2_MAX = 57344.0  # max finite of float8_e5m2


def fp8_scale_from_history(hist: jax.Array, qmax: float) -> jax.Array:
    """Delayed per-tensor scale from an amax history ring: the running
    max of the ring mapped onto ``qmax``. An all-zero (fresh) ring gives
    scale 1 — the first step casts unscaled and seeds the ring."""
    amax = jnp.max(hist)
    return jnp.where(amax > 0, amax / qmax, 1.0).astype(SCALE_DTYPE)


def fp8_push_amax(hist: jax.Array, x: jax.Array) -> jax.Array:
    """Roll the ring one slot and record ``amax(x)`` at slot 0 — the
    in-graph delayed-scaling state update."""
    amax = jnp.max(jnp.abs(x)).astype(hist.dtype)
    return jnp.roll(hist, 1).at[0].set(amax)


def fp8_saturating_cast(
    x: jax.Array, scale: jax.Array, wire_dtype, qmax: float
) -> jax.Array:
    """``x / scale`` clipped into the wire dtype's finite range, then
    cast. Saturation (not overflow-to-inf/nan) is what makes a stale
    delayed scale a graceful error instead of a poisoned step."""
    y = jnp.clip(x.astype(jnp.float32) / scale, -qmax, qmax)
    return y.astype(wire_dtype)


def fp8_matmul(
    x_q: jax.Array,
    w_q: jax.Array,
    scale: jax.Array,
    *,
    impl: Optional[str] = None,
    block_k: int = _MATMUL_BLOCK_K,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``[M, K] x [K, N]`` over fp8 operands with the combined per-tensor
    scale applied at finalize (fp32 accumulation over ``block_k``
    K-tiles). ``impl`` forces ``"jax"``/``"pallas"`` (default: Pallas on
    TPU, the blocked pure-jax twin elsewhere — IDENTICAL accumulation
    order, pinned bit-for-bit by the fast-tier parity test)."""
    m, k = x_q.shape
    k2, n = w_q.shape
    if k2 != k:
        raise ValueError(
            f"fp8_matmul shapes disagree: x {x_q.shape} vs w {w_q.shape}"
        )
    use_pallas = (
        impl == "pallas" if impl else device_platform() == "tpu"
    )
    if use_pallas:
        from .pallas_kernels import fp8_matmul_pallas

        return fp8_matmul_pallas(
            x_q, w_q, scale, block_k=block_k, out_dtype=out_dtype
        )
    # Padding mirrors the Pallas grid exactly (tile clamp, then round up,
    # on every dim) so the reduction tree — and therefore the fp32
    # rounding — matches the kernel bit-for-bit.
    ru = lambda a, b: -(-a // b) * b  # noqa: E731
    bk = min(block_k, ru(k, 128))
    m_pad, n_pad, k_pad = ru(m, 8), ru(n, 128), ru(k, bk)
    xp = jnp.pad(x_q, ((0, m_pad - m), (0, k_pad - k)))
    wp = jnp.pad(w_q, ((0, k_pad - k), (0, n_pad - n)))
    acc = jnp.zeros((m_pad, n_pad), jnp.float32)
    for k0 in range(0, k_pad, bk):
        acc = acc + jax.lax.dot_general(
            xp[:, k0:k0 + bk].astype(jnp.float32),
            wp[k0:k0 + bk].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return (
        acc[:m, :n] * jnp.asarray(scale, jnp.float32)
    ).astype(out_dtype)


def dequantize_blockwise(
    q: jax.Array,
    scales: jax.Array,
    block: Optional[int] = None,
    out_dtype=jnp.float32,
    *,
    impl: Optional[str] = None,
) -> jax.Array:
    """Inverse of :func:`quantize_blockwise` (up to the rounding the wire
    format performed)."""
    if block is None:
        block = default_block()
    n = int(q.shape[0])
    pad = (-n) % block
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad,), q.dtype)])
    rows = q.reshape(-1, block)
    spec_int = jnp.issubdtype(rows.dtype, jnp.integer)
    use_pallas = (
        impl == "pallas"
        if impl
        else (spec_int and block % 128 == 0 and device_platform() == "tpu")
    )
    if use_pallas:
        from .pallas_kernels import dequantize_blockwise_pallas

        out_rows = dequantize_blockwise_pallas(rows, scales)
    else:
        out_rows = rows.astype(jnp.float32) * scales[:, None].astype(
            jnp.float32
        )
    out = out_rows.reshape(-1)
    if pad:
        out = out[:n]
    return out.astype(out_dtype)
