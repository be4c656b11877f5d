"""Pallas TPU kernels for the hot ops: blockwise flash attention.

The reference keeps its hand-written device kernels in
``horovod/common/ops/cuda/cuda_kernels.cu`` (batched fusion-buffer
scatter/gather + fused scaling, SURVEY.md N24); on TPU those particular
jobs are done better by XLA fusion (see ``ops/fusion.py``).  The hot op
that *does* deserve a hand kernel on TPU is attention — the inner block of
ring/sequence parallelism (``parallel/sp.py``) and of every transformer
model in ``models/``.  This module provides it:

* :func:`flash_attention` — blockwise online-softmax attention
  (Dao et al., FlashAttention) as a Pallas kernel: Q blocks stay resident
  in VMEM, K/V stream through in ``block_k`` tiles, the MXU sees
  ``[block_q, d] x [d, block_k]`` matmuls, and the S×S score matrix is
  never materialized in HBM.
* :func:`flash_attention_with_lse` — same kernel, additionally returning
  the per-row log-sum-exp.  ``(out, lse)`` pairs are the composable form:
  ring attention merges one pair per ring hop with
  :func:`combine_blocks`, so the Pallas kernel is the per-step compute of
  the sequence-parallel path too.

Causality across ring steps needs *global* positions, so the kernel takes
``q_offset``/``kv_offset`` (traced scalars, prefetched to SMEM): block r
of an ``sp``-sharded sequence holds global rows ``r*S .. (r+1)*S-1``.

Backward is a pair of Pallas kernels recomputing probabilities from the
saved ``lse`` (the standard flash residual trick): exact, O(S) residual
memory, K/V and Q tiles streamed through VMEM like the forward, and it
handles cotangents for both outputs (``lse`` receives real gradients
through the ring combination weights).

Where the world's devices are not TPUs (``context.device_platform``: the
CPU test mesh) the kernels run in Pallas interpret mode automatically.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..context import device_platform

_SMEM = pltpu.SMEM
_VMEM = pltpu.VMEM

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "combine_blocks",
    "quantize_blockwise_pallas",
    "dequantize_blockwise_pallas",
    "fused_adamw_update_pallas",
    "int8_matmul_pallas",
    "fp8_matmul_pallas",
]

_NEG_INF = float(np.finfo(np.float32).min)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _use_interpret() -> bool:
    return device_platform() != "tpu"


def _head_group(h: int, block_q: int, block_k: int, d: int) -> int:
    """Heads per program.  At short sequences a single head's two
    ``d``-thin matmuls underfill the MXU pipeline and per-program overhead
    (scalar DMAs, grid bookkeeping) dominates, so each program handles a
    group of heads (static unroll).  VMEM budget (~16 MB/core): the fp32
    accumulator and double-buffered q/k/v/o blocks scale with the group,
    and the compiler stacks per-head fp32 score transients on top, so cap
    the estimated block working set at ~4 MB (g=12 at S=512, D=64
    measured 18.4 MB of scoped vmem — over the 16 MB limit) and divide
    ``h`` evenly."""
    for g in (12, 8, 6, 4, 3, 2):
        if h % g:
            continue
        acc = g * block_q * d * 4
        blocks = 2 * g * (block_q + 2 * block_k + block_q) * d * 2
        if acc + blocks <= 4 << 20:
            return g
    return 1


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _head(ref, g, d, packed):
    """Per-head block accessor.  ``packed=False``: heads on a leading
    block dim (``ref[0, g]`` — page-select slice).  ``packed=True``:
    heads packed in the minor (lane) axis of a ``[1, rows, G*d]`` block —
    a static lane slice at ``g*d`` (Mosaic supports 64-aligned lane
    slicing; probed on v5e), which lets q/k/v arrive in the projection's
    native ``[B, S, H*D]`` layout with no relayout anywhere."""
    if packed:
        return ref[0, :, g * d:(g + 1) * d]
    return ref[0, g]


def _head_store(ref, g, d, packed, value):
    if packed:
        ref[0, :, g * d:(g + 1) * d] = value
    else:
        ref[0, g] = value


def _fwd_kernel(
    qoff_ref,
    kvoff_ref,
    kvlen_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    causal: bool,
    masked: bool,
    packed: bool = False,
    d: int = 0,
):
    """One (batch*head group, q-block, k-block) grid step of the online
    softmax.

    The K/V loop is the innermost grid dimension, so only one
    ``[block_k, d]`` K and V tile per head is VMEM-resident at a time —
    sequence length is bounded by HBM, not VMEM.  The running state
    (acc/m/l scratch) persists across the sequentially-executed k steps
    of each (bh-group, qi) program; k step 0 initializes it, the last k
    step normalizes into the outputs.

    Each program handles one batch element and ``G`` heads: at short
    sequence lengths a single head's two ``d``-thin matmuls underfill the
    MXU pipeline and per-program overhead (scalar DMAs, grid bookkeeping)
    dominates — measured 2.3 µs/program against ~0.7 µs of compute at
    S=512, D=64.  Grouping amortizes that overhead G-fold; the per-head
    loop below is a static unroll.  Heads sit on a LEADING block dim
    (page-select slicing — Mosaic cannot relayout a middle-axis slice).

    q_ref: [1, G, block_q, d]; k_ref/v_ref: [1, G, block_k, d];
    o_ref: [1, G, block_q, d]; lse_ref: [1, G, 8, block_q] (8 = min
    sublane tile; caller reads sublane 0).
    """
    q_off = qoff_ref[0, 0]
    kv_off = kvoff_ref[0, 0]
    kv_len = kvlen_ref[0, 0]

    if packed:
        group = q_ref.shape[2] // d
        block_q = q_ref.shape[1]
        block_k = k_ref.shape[1]
    else:
        group = q_ref.shape[1]
        block_q = q_ref.shape[2]
        block_k = k_ref.shape[2]
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:, :, :] = jnp.zeros_like(acc_ref)
        m_ref[:, :, :] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:, :, :] = jnp.zeros_like(l_ref)

    # Causal speedup: skip K/V tiles entirely in this Q block's future.
    q_max = q_off + (qi + 1) * block_q - 1
    kv_min = kv_off + kj * block_k
    run = (kv_min <= q_max) if causal else (kj >= 0)

    @pl.when(run)
    def _update():
        # Geometry shared by every head in the group.  ``masked`` is
        # static: non-causal, unpadded calls skip the validity-mask
        # passes entirely — the kernel is VPU-bound at short S, so every
        # elementwise pass over the [block_q, block_k] scores counts.
        if masked:
            q_pos = q_off + qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0
            )
            col = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            valid = col < kv_len  # mask K/V padding
            if causal:
                valid = jnp.logical_and(valid, q_pos >= kv_off + col)

        for g in range(group):
            # Matmul inputs stay in their storage dtype (bf16 on TPU):
            # the MXU is native bf16xbf16->fp32; upcasting to fp32 first
            # costs ~4-6 MXU passes per dot (measured 15% kernel
            # efficiency before this).  Softmax statistics are fp32.
            s = jax.lax.dot_general(
                _head(q_ref, g, d, packed),
                _head(k_ref, g, d, packed),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [block_q, block_k] fp32
            if masked:
                s = jnp.where(valid, s, _NEG_INF)

            m = m_ref[g, :, :]
            l = l_ref[g, :, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # m_new == NEG_INF only for rows with no valid column so far;
            # keep exponent args finite there (p is zeroed by the mask).
            m_safe = jnp.maximum(m_new, _NEG_INF / 2) if masked else m_new
            p = jnp.exp(s - m_safe)
            if masked:
                p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(m - m_safe)
            l_ref[g, :, :] = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[g, :, :] = m_new
            # p in the V dtype for a native-MXU dot (fp32 accumulate
            # keeps the reduction exact; the p rounding is the standard
            # flash trade).
            acc_ref[g, :, :] = acc_ref[g, :, :] * corr + jax.lax.dot_general(
                p.astype(v_ref.dtype),
                _head(v_ref, g, d, packed),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(kj == nk - 1)
    def _finalize():
        for g in range(group):
            l = l_ref[g, :, :]
            if masked:
                has_any = l > 0.0
                l_safe = jnp.where(has_any, l, 1.0)
                lse = jnp.where(
                    has_any, m_ref[g, :, :] + jnp.log(l_safe), -jnp.inf
                )
            else:
                # Every row saw at least one (unmasked) column: l > 0.
                l_safe = l
                lse = m_ref[g, :, :] + jnp.log(l_safe)
            _head_store(
                o_ref, g, d, packed,
                (acc_ref[g, :, :] / l_safe).astype(o_ref.dtype),
            )
            lse_ref[0, g] = jnp.broadcast_to(
                lse.reshape(1, block_q), (lse_ref.shape[2], block_q)
            )


def _fwd_pallas(
    q,
    k,
    v,
    q_offset,
    kv_offset,
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: Optional[bool],
    n_heads: int = 0,
):
    """Run the kernel.

    Head-major mode (``n_heads=0``): q ``[B,H,Sq,D]``, k/v ``[B,H,Skv,D]``
    → (out ``[B,H,Sq,D]``, lse fp32 ``[B,H,Sq]``).  Heads land on a
    leading block dim (page-select slicing inside the kernel).

    Packed mode (``n_heads=H``): q ``[B,Sq,H*D]``, k/v ``[B,Skv,H*D]`` →
    (out ``[B,Sq,H*D]``, lse ``[B,H,Sq]``) — the projection's native
    layout.  Heads live in the minor (lane) axis and the kernel slices
    them statically (``_head``), so q/k/v/o need **no relayout at all**:
    the r4 head-major path still paid the ``[B,S,H·D]→[B,H,S,D]``
    transpose by letting XLA fold it into the projection dots, which then
    ran at ~43%% of peak (``docs/perf_analysis_bert_r04.md``).
    """
    packed = n_heads > 0
    if packed:
        b, sq, hd = q.shape
        h = n_heads
        d = hd // h
        skv = k.shape[1]
    else:
        b, h, sq, d = q.shape
        skv = k.shape[2]
    if interpret is None:
        interpret = _use_interpret()

    block_q = min(block_q, _round_up(sq, 8))
    block_k = min(block_k, _round_up(skv, 8))
    sq_pad = _round_up(sq, block_q)
    skv_pad = _round_up(skv, block_k)

    seq_axis = 1 if packed else 2

    def pad_seq(x, s, s_pad):
        if s_pad != s:
            pads = [(0, 0)] * x.ndim
            pads[seq_axis] = (0, s_pad - s)
            x = jnp.pad(x, pads)
        return x

    qr = pad_seq(q, sq, sq_pad)
    kr = pad_seq(k, skv, skv_pad)
    vr = pad_seq(v, skv, skv_pad)
    scalars = [
        jnp.asarray(x, jnp.int32).reshape(1, 1)
        for x in (q_offset, kv_offset, skv)
    ]

    group = _head_group(h, block_q, block_k, d)
    grid = (b, h // group, sq_pad // block_q, skv_pad // block_k)
    smem_spec = pl.BlockSpec(
        (1, 1), lambda bi, hi, qi, kj: (0, 0), memory_space=_SMEM
    )

    def vspec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=_VMEM)

    scratch = [
        _VMEM((group, block_q, d), jnp.float32),
        _VMEM((group, block_q, 1), jnp.float32),
        _VMEM((group, block_q, 1), jnp.float32),
    ]

    if packed:
        q_spec = vspec(
            (1, block_q, group * d), lambda bi, hi, qi, kj: (bi, qi, hi)
        )
        kv_spec = vspec(
            (1, block_k, group * d), lambda bi, hi, qi, kj: (bi, kj, hi)
        )
        o_spec = q_spec
        o_shape = jax.ShapeDtypeStruct((b, sq_pad, h * d), q.dtype)
    else:
        q_spec = vspec(
            (1, group, block_q, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)
        )
        kv_spec = vspec(
            (1, group, block_k, d), lambda bi, hi, qi, kj: (bi, hi, kj, 0)
        )
        o_spec = q_spec
        o_shape = jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal,
            masked=causal or skv_pad != skv, packed=packed, d=d,
        ),
        grid=grid,
        in_specs=[smem_spec, smem_spec, smem_spec, q_spec, kv_spec, kv_spec],
        out_specs=[
            o_spec,
            vspec((1, group, 8, block_q), lambda bi, hi, qi, kj: (bi, hi, 0, qi)),
        ],
        out_shape=[
            o_shape,
            jax.ShapeDtypeStruct((b, h, 8, sq_pad), jnp.float32),
        ],
        scratch_shapes=scratch,
        # batch/head/qi programs are independent; only the K/V stream (kj)
        # carries state — lets Mosaic parallelize/pipeline the outer grid.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq_pad * skv_pad * d,
            bytes_accessed=(qr.size + kr.size + vr.size) * qr.dtype.itemsize
            + b * h * sq_pad * d * qr.dtype.itemsize,
            transcendentals=b * h * sq_pad * skv_pad,
        ),
        interpret=interpret,
        name="hvd_flash_fwd",
    )(*scalars, qr, kr, vr)

    if packed:
        out = out[:, :sq]  # [B,Sq,H*D]
    else:
        out = out[:, :, :sq]  # [B,H,Sq,D]
    lse = lse[:, :, 0, :sq]  # [B,H,Sq]
    return out, lse


# ---------------------------------------------------------------------------
# Backward: two Pallas kernels recomputing p from the saved lse (the flash
# residual trick).  dk/dv streams Q blocks per K tile; dq streams K tiles
# per Q block.  Standard flash gradients, plus the ``g_lse`` term (``lse``
# receives real cotangents through ring attention's combine weights):
#     p  = exp(s - lse)           (masked)
#     ds = p ⊙ (dP − Δ) + g_lse ⊙ p,   Δ = rowsum(g ⊙ out)
#     dq = ds·K·scale, dk = dsᵀ·Q·scale, dv = pᵀ·g
# ---------------------------------------------------------------------------


def _recompute_p_ds(qoff_ref, kvoff_ref, kvlen_ref, lse_ref, delta_ref,
                    glse_ref, q_ref, k_ref, v_ref, g_ref, qi, kj, g, *,
                    sm_scale: float, causal: bool, masked: bool,
                    packed: bool = False, d: int = 0):
    """Shared per-(q-block, k-tile, head) recompute: returns
    (p, ds, q_blk, g_blk).

    Padded / fully-masked Q rows carry ``lse == -inf`` and zero ``g``;
    ``row_ok`` zeroes their ``p`` so they contribute nothing.
    """
    block_q = q_ref.shape[1] if packed else q_ref.shape[2]
    block_k = k_ref.shape[1] if packed else k_ref.shape[2]
    # Storage-dtype (bf16) matmul inputs with fp32 accumulation — see the
    # forward kernel note; only the softmax/ds algebra runs in fp32.
    q_blk = _head(q_ref, g, d, packed)
    g_blk = _head(g_ref, g, d, packed)
    k_blk = _head(k_ref, g, d, packed)
    v_blk = _head(v_ref, g, d, packed)

    s = jax.lax.dot_general(
        q_blk,
        k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # [block_q, block_k] fp32

    lse_row = lse_ref[0, g, 0, :].reshape(block_q, 1)
    if masked:
        col = kj * block_k + lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        valid = col < kvlen_ref[0, 0]
        if causal:
            q_pos = qoff_ref[0, 0] + qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0
            )
            valid = jnp.logical_and(valid, q_pos >= kvoff_ref[0, 0] + col)
        row_ok = lse_row > _NEG_INF / 4  # -inf rows: no valid keys anywhere
        lse_safe = jnp.where(row_ok, lse_row, 0.0)
        p = jnp.where(
            jnp.logical_and(valid, row_ok), jnp.exp(s - lse_safe), 0.0
        )
    else:
        p = jnp.exp(s - lse_row)

    dp = jax.lax.dot_general(
        g_blk,
        v_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    delta_row = delta_ref[0, g, 0, :].reshape(block_q, 1)
    glse_row = glse_ref[0, g, 0, :].reshape(block_q, 1)
    ds = p * (dp - delta_row) + glse_row * p
    return p, ds, q_blk, g_blk


def _bwd_kernel_dkdv(
    qoff_ref, kvoff_ref, kvlen_ref, lse_ref, delta_ref, glse_ref,
    q_ref, k_ref, v_ref, g_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, sm_scale: float, causal: bool, masked: bool,
    packed: bool = False, d: int = 0,
):
    """grid (b, h-group, kj, qi): each K tile accumulates over streamed
    Q blocks; the per-head loop is a static unroll (see forward)."""
    qi = pl.program_id(3)
    kj = pl.program_id(2)
    nq = pl.num_programs(3)
    if packed:
        group = q_ref.shape[2] // d
        block_q = q_ref.shape[1]
        block_k = k_ref.shape[1]
    else:
        group = q_ref.shape[1]
        block_q = q_ref.shape[2]
        block_k = k_ref.shape[2]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:, :, :] = jnp.zeros_like(dk_acc)
        dv_acc[:, :, :] = jnp.zeros_like(dv_acc)

    # Causal: Q blocks entirely before this K tile contribute nothing.
    q_max = qoff_ref[0, 0] + (qi + 1) * block_q - 1
    kv_min = kvoff_ref[0, 0] + kj * block_k
    run = (kv_min <= q_max) if causal else (qi >= 0)

    @pl.when(run)
    def _update():
        for g in range(group):
            p, ds, q_blk, g_blk = _recompute_p_ds(
                qoff_ref, kvoff_ref, kvlen_ref, lse_ref, delta_ref,
                glse_ref, q_ref, k_ref, v_ref, g_ref, qi, kj, g,
                sm_scale=sm_scale, causal=causal, masked=masked,
                packed=packed, d=d,
            )
            dv_acc[g, :, :] = dv_acc[g, :, :] + jax.lax.dot_general(
                p.astype(g_blk.dtype), g_blk,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_acc[g, :, :] = dk_acc[g, :, :] + jax.lax.dot_general(
                ds.astype(q_blk.dtype), q_blk,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale

    @pl.when(qi == nq - 1)
    def _finalize():
        for g in range(group):
            _head_store(
                dk_ref, g, d, packed, dk_acc[g, :, :].astype(dk_ref.dtype)
            )
            _head_store(
                dv_ref, g, d, packed, dv_acc[g, :, :].astype(dv_ref.dtype)
            )


def _bwd_kernel_dq(
    qoff_ref, kvoff_ref, kvlen_ref, lse_ref, delta_ref, glse_ref,
    q_ref, k_ref, v_ref, g_ref, dq_ref, dq_acc,
    *, sm_scale: float, causal: bool, masked: bool,
    packed: bool = False, d: int = 0,
):
    """grid (b, h-group, qi, kj): each Q block accumulates over streamed
    K tiles; the per-head loop is a static unroll (see forward)."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    if packed:
        group = q_ref.shape[2] // d
        block_q = q_ref.shape[1]
        block_k = k_ref.shape[1]
    else:
        group = q_ref.shape[1]
        block_q = q_ref.shape[2]
        block_k = k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        dq_acc[:, :, :] = jnp.zeros_like(dq_acc)

    q_max = qoff_ref[0, 0] + (qi + 1) * block_q - 1
    kv_min = kvoff_ref[0, 0] + kj * block_k
    run = (kv_min <= q_max) if causal else (kj >= 0)

    @pl.when(run)
    def _update():
        for g in range(group):
            _, ds, _, _ = _recompute_p_ds(
                qoff_ref, kvoff_ref, kvlen_ref, lse_ref, delta_ref,
                glse_ref, q_ref, k_ref, v_ref, g_ref, qi, kj, g,
                sm_scale=sm_scale, causal=causal, masked=masked,
                packed=packed, d=d,
            )
            k_blk = _head(k_ref, g, d, packed)
            dq_acc[g, :, :] = dq_acc[g, :, :] + jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale

    @pl.when(kj == nk - 1)
    def _finalize():
        for g in range(group):
            _head_store(
                dq_ref, g, d, packed, dq_acc[g, :, :].astype(dq_ref.dtype)
            )


def _bwd_pallas(
    q, k, v, q_offset, kv_offset, out, lse, g_out, g_lse, *,
    sm_scale: float, causal: bool, block_q: int, block_k: int,
    interpret: Optional[bool], n_heads: int = 0,
):
    packed = n_heads > 0
    if packed:
        b, sq, hd = q.shape
        h = n_heads
        d = hd // h
        skv = k.shape[1]
    else:
        b, h, sq, d = q.shape
        skv = k.shape[2]
    if interpret is None:
        interpret = _use_interpret()
    block_q = min(block_q, _round_up(sq, 8))
    block_k = min(block_k, _round_up(skv, 8))
    sq_pad = _round_up(sq, block_q)
    skv_pad = _round_up(skv, block_k)

    seq_axis = 1 if packed else 2

    def pad_seq(x, s, s_pad):
        if s_pad != s:
            pads = [(0, 0)] * x.ndim
            pads[seq_axis] = (0, s_pad - s)
            x = jnp.pad(x, pads)
        return x

    qr = pad_seq(q, sq, sq_pad)
    kr = pad_seq(k, skv, skv_pad)
    vr = pad_seq(v, skv, skv_pad)
    gr = pad_seq(g_out.astype(q.dtype), sq, sq_pad)

    # Row statistics in the kernel's [b, h, 8, sq_pad] layout (8 = min
    # sublane tile; kernels read sublane 0).
    def rows(x, pad_value):
        x = x.reshape(b, h, sq)
        if sq_pad != sq:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, sq_pad - sq)),
                        constant_values=pad_value)
        return jnp.broadcast_to(x[:, :, None, :], (b, h, 8, sq_pad))

    if packed:
        # [B,S,H*D] → per-head row dot via a free reshape (no transpose).
        delta = jnp.einsum(
            "bqhd,bqhd->bhq",
            g_out.astype(jnp.float32).reshape(b, sq, h, d),
            out.astype(jnp.float32).reshape(b, sq, h, d),
        )
    else:
        delta = jnp.einsum(
            "bhqd,bhqd->bhq",
            g_out.astype(jnp.float32),
            out.astype(jnp.float32),
        )
    lse_rows = rows(lse, -jnp.inf)  # padded rows masked via row_ok
    delta_rows = rows(delta, 0.0)
    glse = jnp.zeros((b, h, sq), jnp.float32) if g_lse is None else g_lse
    glse_rows = rows(glse.astype(jnp.float32), 0.0)

    scalars = [
        jnp.asarray(x, jnp.int32).reshape(1, 1)
        for x in (q_offset, kv_offset, skv)
    ]

    smem_spec = pl.BlockSpec(
        (1, 1), lambda *_: (0, 0),
        memory_space=_SMEM,
    )

    def vspec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=_VMEM)

    group = _head_group(h, block_q, block_k, d)
    common_params = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
        interpret=interpret,
    )

    def q_spec(index_map_qi):
        if packed:
            return vspec((1, block_q, group * d), index_map_qi)
        return vspec((1, group, block_q, d), index_map_qi)

    def kv_spec(index_map_kj):
        if packed:
            return vspec((1, block_k, group * d), index_map_kj)
        return vspec((1, group, block_k, d), index_map_kj)

    if packed:
        # [B, S, H*D] packed blocks: seq index first, head index last.
        qmap_kv_grid = lambda bi, hi, kj, qi: (bi, qi, hi)  # noqa: E731
        kmap_kv_grid = lambda bi, hi, kj, qi: (bi, kj, hi)  # noqa: E731
        qmap_q_grid = lambda bi, hi, qi, kj: (bi, qi, hi)  # noqa: E731
        kmap_q_grid = lambda bi, hi, qi, kj: (bi, kj, hi)  # noqa: E731
        dkv_shape = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            (b, skv_pad, h * d), x.dtype
        )
        dq_shape = jax.ShapeDtypeStruct((b, sq_pad, h * d), q.dtype)
    else:
        qmap_kv_grid = lambda bi, hi, kj, qi: (bi, hi, qi, 0)  # noqa: E731
        kmap_kv_grid = lambda bi, hi, kj, qi: (bi, hi, kj, 0)  # noqa: E731
        qmap_q_grid = lambda bi, hi, qi, kj: (bi, hi, qi, 0)  # noqa: E731
        kmap_q_grid = lambda bi, hi, qi, kj: (bi, hi, kj, 0)  # noqa: E731
        dkv_shape = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            (b, h, skv_pad, d), x.dtype
        )
        dq_shape = jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype)

    # dk/dv: grid (b, h-group, kj, qi) — q streams innermost.
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel_dkdv, sm_scale=sm_scale, causal=causal,
            masked=causal or skv_pad != skv or sq_pad != sq,
            packed=packed, d=d,
        ),
        grid=(b, h // group, skv_pad // block_k, sq_pad // block_q),
        in_specs=[
            smem_spec, smem_spec, smem_spec,
            vspec((1, group, 8, block_q), lambda bi, hi, kj, qi: (bi, hi, 0, qi)),
            vspec((1, group, 8, block_q), lambda bi, hi, kj, qi: (bi, hi, 0, qi)),
            vspec((1, group, 8, block_q), lambda bi, hi, kj, qi: (bi, hi, 0, qi)),
            q_spec(qmap_kv_grid),
            kv_spec(kmap_kv_grid),
            kv_spec(kmap_kv_grid),
            q_spec(qmap_kv_grid),
        ],
        out_specs=[
            kv_spec(kmap_kv_grid),
            kv_spec(kmap_kv_grid),
        ],
        out_shape=[dkv_shape(k), dkv_shape(v)],
        scratch_shapes=[
            _VMEM((group, block_k, d), jnp.float32),
            _VMEM((group, block_k, d), jnp.float32),
        ],
        **common_params,
        name="hvd_flash_bwd_dkv",
    )(*scalars, lse_rows, delta_rows, glse_rows, qr, kr, vr, gr)

    # dq: grid (b, h-group, qi, kj) — k streams innermost.
    dq = pl.pallas_call(
        functools.partial(
            _bwd_kernel_dq, sm_scale=sm_scale, causal=causal,
            masked=causal or skv_pad != skv or sq_pad != sq,
            packed=packed, d=d,
        ),
        grid=(b, h // group, sq_pad // block_q, skv_pad // block_k),
        in_specs=[
            smem_spec, smem_spec, smem_spec,
            vspec((1, group, 8, block_q), lambda bi, hi, qi, kj: (bi, hi, 0, qi)),
            vspec((1, group, 8, block_q), lambda bi, hi, qi, kj: (bi, hi, 0, qi)),
            vspec((1, group, 8, block_q), lambda bi, hi, qi, kj: (bi, hi, 0, qi)),
            q_spec(qmap_q_grid),
            kv_spec(kmap_q_grid),
            kv_spec(kmap_q_grid),
            q_spec(qmap_q_grid),
        ],
        out_specs=q_spec(qmap_q_grid),
        out_shape=dq_shape,
        scratch_shapes=[_VMEM((group, block_q, d), jnp.float32)],
        **common_params,
        name="hvd_flash_bwd_dq",
    )(*scalars, lse_rows, delta_rows, glse_rows, qr, kr, vr, gr)

    if packed:
        return (
            dq[:, :sq].astype(q.dtype),
            dk[:, :skv].astype(k.dtype),
            dv[:, :skv].astype(v.dtype),
        )
    return (
        dq[:, :, :sq].astype(q.dtype),
        dk[:, :, :skv].astype(k.dtype),
        dv[:, :, :skv].astype(v.dtype),
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, q_offset, kv_offset, sm_scale, causal, block_q, block_k,
           interpret, n_heads=0):
    return _fwd_pallas(
        q,
        k,
        v,
        q_offset,
        kv_offset,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        n_heads=n_heads,
    )


def _flash_fwd(q, k, v, q_offset, kv_offset, sm_scale, causal, block_q,
               block_k, interpret, n_heads=0):
    out, lse = _flash(
        q, k, v, q_offset, kv_offset, sm_scale, causal, block_q, block_k,
        interpret, n_heads
    )
    return (out, lse), (q, k, v, q_offset, kv_offset, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, n_heads, res, g):
    q, k, v, q_offset, kv_offset, out, lse = res
    g_out, g_lse = g
    dq, dk, dv = _bwd_pallas(
        q,
        k,
        v,
        q_offset,
        kv_offset,
        out,
        lse,
        g_out,
        g_lse,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        n_heads=n_heads,
    )
    # Integer offsets take float0 cotangents.
    zero = np.zeros((), dtype=jax.dtypes.float0)
    return dq, dk, dv, zero, zero


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def flash_attention_with_lse(
    q,
    k,
    v,
    *,
    causal: bool = False,
    q_offset=0,
    kv_offset=0,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    layout: str = "bshd",
    n_heads: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Blockwise attention returning ``(out, lse)``.

    ``layout="bshd"`` (default): q ``[B, Sq, H, D]``, k/v
    ``[B, Skv, H, D]``.  ``layout="bhsd"``: head-major ``[B, H, S, D]``
    — heads on a leading block dim.  ``layout="bsm"``: packed
    ``[B, S, H*D]`` with ``n_heads`` given — the projection's native
    layout; heads are sliced from the minor axis inside the kernel, so
    q/k/v/out need no relayout at all (the r4 ``bhsd`` path still paid
    the head transpose by folding it into the projection dots, which
    then ran at ~43%% of MXU peak — ``docs/perf_analysis_bert_r04.md``).
    ``lse`` is fp32 ``[B, H, Sq]`` in every layout — the log-sum-exp of
    each row's (masked) scores, the residual needed to merge partial
    attention across K/V shards (:func:`combine_blocks`) and to run the
    exact backward.  ``q_offset``/``kv_offset`` are the global positions
    of row 0 (may be traced), used only for causal masking.
    """
    packed = layout == "bsm"
    if packed and n_heads <= 0:
        raise ValueError("layout='bsm' requires n_heads")
    if packed and (q.shape[-1] // n_heads) % 64 != 0 and not (
        interpret if interpret is not None else _use_interpret()
    ):
        raise ValueError(
            "layout='bsm' needs head_dim % 64 == 0 on TPU (Mosaic lane "
            f"slicing is 64-aligned); got head_dim="
            f"{q.shape[-1] // n_heads} — use layout='bhsd'"
        )
    if sm_scale is None:
        d = q.shape[-1] // n_heads if packed else q.shape[-1]
        sm_scale = 1.0 / float(np.sqrt(d))
    if layout == "bshd":
        q = jnp.moveaxis(q, 2, 1)
        k = jnp.moveaxis(k, 2, 1)
        v = jnp.moveaxis(v, 2, 1)
    elif layout not in ("bhsd", "bsm"):
        raise ValueError(
            f"layout must be 'bshd', 'bhsd' or 'bsm', got {layout!r}"
        )
    out, lse = _flash(
        q,
        k,
        v,
        jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(kv_offset, jnp.int32),
        float(sm_scale),
        bool(causal),
        int(block_q),
        int(block_k),
        interpret,
        int(n_heads) if packed else 0,
    )
    if layout == "bshd":
        out = jnp.moveaxis(out, 1, 2)
    return out, lse


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    mask=None,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    layout: str = "bshd",
    n_heads: int = 0,
) -> jax.Array:
    """Drop-in memory-efficient replacement for
    ``models.transformer.dot_product_attention`` (same signature shape).

    Dense ``mask`` is not supported by the blockwise kernel — callers that
    need one fall back to the XLA path.
    """
    if mask is not None:
        raise ValueError(
            "flash_attention supports causal masking only; pass mask=None "
            "or use dot_product_attention"
        )
    out, _ = flash_attention_with_lse(
        q,
        k,
        v,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        layout=layout,
        n_heads=n_heads,
    )
    return out


# ---------------------------------------------------------------------------
# Blockwise quantization kernels (the int8 wire format of the quantized
# collectives, ops/quantization.py).  One VMEM pass per row tile: per-row
# (= per-block) max-abs scale, round, cast — no separate reduction pass
# over HBM.  Scales are emitted in a [8, n_blocks] layout (8 = min f32
# sublane tile, rows identical; callers read row 0) so the lane axis
# carries the blocks and the output tiles legally at any block count.
# The pure-jax twin lives in ops/quantization.py; the CPU-interpreter
# parity test pins the two together (tests/test_quantization.py).
# ---------------------------------------------------------------------------

_QUANT_TILE_ROWS = 128  # blocks (rows) per program; lane-legal scales tile


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float, integer: bool):
    x = x_ref[...].astype(jnp.float32)  # [R, B]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    y = x / scale
    if integer:
        q = jnp.clip(jnp.round(y), -qmax, qmax)
    else:
        q = y
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = jnp.broadcast_to(
        scale.reshape(1, -1), (s_ref.shape[0], scale.shape[0])
    )


def _dequant_kernel(q_ref, s_ref, out_ref):
    scale = s_ref[0, :].reshape(-1, 1)  # [R, 1]
    out_ref[...] = (
        q_ref[...].astype(jnp.float32) * scale
    ).astype(out_ref.dtype)


def _quant_grid(n_blocks: int):
    rows = min(_QUANT_TILE_ROWS, _round_up(n_blocks, 8))
    return rows, _round_up(n_blocks, rows)


def quantize_blockwise_pallas(
    rows, *, qmax: float, wire_dtype, integer: bool = True,
    interpret: Optional[bool] = None,
):
    """``[n_blocks, block]`` -> ``(q [n_blocks, block] wire_dtype,
    scales [n_blocks] fp32)``."""
    if interpret is None:
        interpret = _use_interpret()
    nb, block = rows.shape
    r, nb_pad = _quant_grid(nb)
    if nb_pad != nb:
        rows = jnp.pad(rows, ((0, nb_pad - nb), (0, 0)))
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax, integer=integer),
        grid=(nb_pad // r,),
        in_specs=[pl.BlockSpec((r, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((r, block), lambda i: (i, 0)),
            pl.BlockSpec((8, r), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb_pad, block), wire_dtype),
            jax.ShapeDtypeStruct((8, nb_pad), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_quantize_blockwise",
    )(rows)
    return q[:nb], s[0, :nb]


def dequantize_blockwise_pallas(
    q_rows, scales, *, out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
):
    """``([n_blocks, block] wire, [n_blocks] fp32)`` -> fp32 rows."""
    if interpret is None:
        interpret = _use_interpret()
    nb, block = q_rows.shape
    r, nb_pad = _quant_grid(nb)
    if nb_pad != nb:
        q_rows = jnp.pad(q_rows, ((0, nb_pad - nb), (0, 0)))
        scales = jnp.pad(scales, (0, nb_pad - nb))
    s_rows = jnp.broadcast_to(scales.reshape(1, -1), (8, nb_pad))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nb_pad // r,),
        in_specs=[
            pl.BlockSpec((r, block), lambda i: (i, 0)),
            pl.BlockSpec((8, r), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb_pad, block), out_dtype),
        interpret=interpret,
        name="hvd_dequantize_blockwise",
    )(q_rows, s_rows)
    return out[:nb]


# ---------------------------------------------------------------------------
# Fused optimizer update (the ZeRO-1 sharded weight update's hot loop).
# One VMEM pass over each flat shard bucket doing the whole AdamW chain —
# moment update, bias correction, weight decay, learning-rate scale and the
# cast back into the parameter's storage dtype — where the unfused optax
# path emits one elementwise HLO per algebra step, each round-tripping the
# shard through HBM.  Math runs in fp32 regardless of the buffer dtypes
# (bf16 moments would round the running EMAs every step); only the stores
# cast.  The pure-jax twin lives in ``optimizer.py``
# (``_fused_adamw_update_jax``) and the fast-tier CPU-interpreter parity
# test (``tests/test_fused_update.py``) pins the two bit-for-bit — the
# same contract the blockwise quantization kernels above carry.
# ---------------------------------------------------------------------------

_ADAM_LANES = 128
_ADAM_TILE_ROWS = 512  # rows/program: 7 buffers x 512x128 fp32 ≈ 1.8 MB VMEM


def _fused_adamw_kernel(
    bc_ref, p_ref, m_ref, v_ref, g_ref, u_ref, mo_ref, vo_ref, *,
    lr: float, b1: float, b2: float, eps: float, eps_root: float,
    weight_decay: float,
):
    """One row-tile of the fused AdamW update.

    Mirrors optax ``adamw`` exactly (``scale_by_adam`` with its
    post-increment bias correction, then ``add_decayed_weights``, then
    the ``-lr`` scale), so ``fused_update=True`` is the same trajectory
    as the unfused reference up to the fp32-vs-storage-dtype rounding.
    Zero-padded tail rows are fixed points: every term is 0 there.
    ``bc_ref`` (SMEM) carries the two bias corrections ``1 - b**count``:
    Mosaic has no scalar ``powf``, so the wrapper computes them.
    """
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    m = (1.0 - b1) * g + b1 * m_ref[...].astype(jnp.float32)
    v = (1.0 - b2) * (g * g) + b2 * v_ref[...].astype(jnp.float32)
    mhat = m / bc_ref[0, 0]
    vhat = v / bc_ref[0, 1]
    u = mhat / (jnp.sqrt(vhat + eps_root) + eps)
    if weight_decay:
        u = u + weight_decay * p
    u_ref[...] = (-lr * u).astype(u_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def fused_adamw_update_pallas(
    p, m, v, g, count, *, lr: float, b1: float = 0.9, b2: float = 0.999,
    eps: float = 1e-8, eps_root: float = 0.0, weight_decay: float = 1e-4,
    interpret: Optional[bool] = None,
):
    """Fused AdamW step over flat 1-D buffers (a ZeRO-1 shard).

    ``(p, m, v, g)`` are same-length flat buffers (param shard, Adam
    moments, reduced gradient shard); ``count`` is the optax step counter
    *before* this update (scalar int32, may be traced).  Returns
    ``(update, new_m, new_v)`` — the update already carries the ``-lr``
    sign and is cast to ``p.dtype`` (bf16 params ride the all-gather in
    bf16), the moments keep their own storage dtypes.  Ragged lengths are
    zero-padded to the row tile and sliced back; the padded lanes are
    exact fixed points of the update algebra.
    """
    if interpret is None:
        interpret = _use_interpret()
    n = int(p.shape[0])
    rows = -(-n // _ADAM_LANES)
    r = min(_ADAM_TILE_ROWS, _round_up(rows, 8))
    rows_pad = _round_up(max(rows, 1), r)
    n_pad = rows_pad * _ADAM_LANES

    def prep(x):
        if n_pad != n:
            x = jnp.pad(x, (0, n_pad - n))
        return x.reshape(rows_pad, _ADAM_LANES)

    c = (jnp.asarray(count, jnp.int32) + 1).astype(jnp.float32)
    bias_corrections = jnp.stack([1.0 - b1 ** c, 1.0 - b2 ** c]).reshape(1, 2)
    smem_spec = pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=_SMEM)
    tile = pl.BlockSpec((r, _ADAM_LANES), lambda i: (i, 0))
    u, nm, nv = pl.pallas_call(
        functools.partial(
            _fused_adamw_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
            eps_root=eps_root, weight_decay=weight_decay,
        ),
        grid=(rows_pad // r,),
        in_specs=[smem_spec, tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, _ADAM_LANES), p.dtype),
            jax.ShapeDtypeStruct((rows_pad, _ADAM_LANES), m.dtype),
            jax.ShapeDtypeStruct((rows_pad, _ADAM_LANES), v.dtype),
        ],
        interpret=interpret,
        name="fused_adamw_update",
    )(bias_corrections, prep(p), prep(m), prep(v), prep(g))
    return (
        u.reshape(-1)[:n],
        nm.reshape(-1)[:n],
        nv.reshape(-1)[:n],
    )


# ---------------------------------------------------------------------------
# int8 weight matmul (the serving plane's W8A16 path).  Weights sit in HBM
# as int8 with per-output-channel fp32 scales (quantized ONCE at ServePool
# checkpoint load via the blockwise codec, ops/quantization.quantize_weight)
# and are cast to the activation dtype in-register per tile — the scales
# are applied inside the kernel at finalize, so no dequantized fp copy of
# the weights ever exists in HBM.  At serving batch sizes the matmuls are
# weight-bandwidth-bound, so halving the weight bytes is the win.  The
# pure-jax twin (same block_k accumulation order, so the fp32 sums are
# bit-identical) lives in ops/quantization.int8_weight_matmul.
# ---------------------------------------------------------------------------


def _int8_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        x_ref[...],
        w_ref[...].astype(x_ref.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (
            acc_ref[...] * s_ref[0, :].reshape(1, -1)
        ).astype(o_ref.dtype)


def int8_matmul_pallas(
    x, w_q, scales, *, block_m: int = 256, block_n: int = 256,
    block_k: int = 256, out_dtype=None, interpret: Optional[bool] = None,
):
    """``[M, K] x [K, N] int8 -> [M, N]`` with per-column fp32 scales
    applied at finalize (fp32 accumulation over ``block_k`` K-tiles).

    ``scales`` has shape ``[N]`` — one scale per output channel, the
    layout :func:`horovod_tpu.ops.quantization.quantize_weight` emits.
    """
    if interpret is None:
        interpret = _use_interpret()
    if out_dtype is None:
        out_dtype = x.dtype
    mm, kk = x.shape
    kk2, nn = w_q.shape
    if kk2 != kk or scales.shape != (nn,):
        raise ValueError(
            f"int8_matmul shapes disagree: x {x.shape}, w {w_q.shape}, "
            f"scales {scales.shape}"
        )
    bm = min(block_m, _round_up(mm, 8))
    bn = min(block_n, _round_up(nn, 128))
    bk = min(block_k, _round_up(kk, 128))
    m_pad, n_pad, k_pad = (
        _round_up(mm, bm), _round_up(nn, bn), _round_up(kk, bk)
    )

    def pad2(a, r, c):
        if a.shape != (r, c):
            a = jnp.pad(a, ((0, r - a.shape[0]), (0, c - a.shape[1])))
        return a

    xr = pad2(x, m_pad, k_pad)
    wr = pad2(w_q, k_pad, n_pad)
    # Scales in the [8, n] sublane-tiled layout the quant kernels use
    # (rows identical; kernel reads sublane 0).
    s_rows = jnp.broadcast_to(
        jnp.pad(scales, (0, n_pad - nn)).reshape(1, -1), (8, n_pad)
    )
    out = pl.pallas_call(
        _int8_matmul_kernel,
        grid=(m_pad // bm, n_pad // bn, k_pad // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((8, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype),
        scratch_shapes=[_VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad * n_pad * k_pad,
            bytes_accessed=xr.size * xr.dtype.itemsize
            + wr.size
            + m_pad * n_pad * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
        name="int8_matmul",
    )(xr, wr, s_rows)
    return out[:mm, :nn]


# ---------------------------------------------------------------------------
# fp8 training matmul (the compute-precision face of the blockwise codec,
# HVDTPU_COMPUTE_DTYPE=fp8).  Both operands arrive already saturating-cast
# to fp8 (e4m3 forward, e5m2 for the incoming gradient in backward) under
# per-tensor delayed scales; the kernel upcasts tiles in-register, runs the
# blocked fp32 accumulation, and applies the ONE combined scalar scale
# (sx*sk, SMEM) at finalize — no dequantized fp copy of either operand
# exists in HBM.  The pure-jax twin (identical block_k accumulation order,
# bit-identical fp32 sums) lives in ops/quantization.fp8_matmul.
# ---------------------------------------------------------------------------


def _fp8_matmul_kernel(s_ref, x_ref, w_ref, o_ref, acc_ref):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] * s_ref[0, 0]).astype(o_ref.dtype)


def fp8_matmul_pallas(
    x_q, w_q, scale, *, block_m: int = 256, block_n: int = 256,
    block_k: int = 256, out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
):
    """``[M, K] x [K, N]`` fp8 -> ``[M, N]`` with one per-tensor-pair
    fp32 scale applied at finalize (fp32 accumulation over ``block_k``
    K-tiles).

    ``x_q``/``w_q`` are fp8 (``float8_e4m3fn`` or ``float8_e5m2``, mixed
    flavors allowed — backward pairs an e5m2 gradient with e4m3
    residuals); ``scale`` is the scalar product of the two per-tensor
    delayed scales.  Zero padding of ragged edges is exact: fp8 zero
    upcasts to fp32 zero.
    """
    if interpret is None:
        interpret = _use_interpret()
    mm, kk = x_q.shape
    kk2, nn = w_q.shape
    if kk2 != kk:
        raise ValueError(
            f"fp8_matmul shapes disagree: x {x_q.shape}, w {w_q.shape}"
        )
    bm = min(block_m, _round_up(mm, 8))
    bn = min(block_n, _round_up(nn, 128))
    bk = min(block_k, _round_up(kk, 128))
    m_pad, n_pad, k_pad = (
        _round_up(mm, bm), _round_up(nn, bn), _round_up(kk, bk)
    )

    def pad2(a, r, c):
        if a.shape != (r, c):
            a = jnp.pad(a, ((0, r - a.shape[0]), (0, c - a.shape[1])))
        return a

    xr = pad2(x_q, m_pad, k_pad)
    wr = pad2(w_q, k_pad, n_pad)
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    smem_spec = pl.BlockSpec(
        (1, 1), lambda mi, ni, ki: (0, 0),
        memory_space=_SMEM,
    )
    out = pl.pallas_call(
        _fp8_matmul_kernel,
        grid=(m_pad // bm, n_pad // bn, k_pad // bk),
        in_specs=[
            smem_spec,
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype),
        scratch_shapes=[_VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad * n_pad * k_pad,
            bytes_accessed=xr.size
            + wr.size
            + m_pad * n_pad * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
        name="fp8_matmul",
    )(scale, xr, wr)
    return out[:mm, :nn]


def combine_blocks(o_acc, lse_acc, o_i, lse_i):
    """Merge a new partial-attention ``(o_i, lse_i)`` into the running
    ``(o_acc, lse_acc)``.

    Both ``o`` are normalized outputs ``[B,S,H,D]``; ``lse`` fp32
    ``[B,H,S]``.  Exact: the true numerator of block *i* is
    ``o_i * exp(lse_i)``, so the merged output is the lse-weighted convex
    combination.  This is the per-hop update of Pallas-backed ring
    attention (``parallel/sp.py``).
    """
    lse_new = jnp.logaddexp(lse_acc, lse_i)
    # Fully-masked-so-far rows: -inf - -inf → guard to 0 weight.
    w_acc = jnp.where(
        jnp.isfinite(lse_acc), jnp.exp(lse_acc - lse_new), 0.0
    )
    w_i = jnp.where(jnp.isfinite(lse_i), jnp.exp(lse_i - lse_new), 0.0)
    wa = w_acc.transpose(0, 2, 1)[..., None].astype(o_acc.dtype)
    wi = w_i.transpose(0, 2, 1)[..., None].astype(o_i.dtype)
    return o_acc * wa + o_i * wi, lse_new
