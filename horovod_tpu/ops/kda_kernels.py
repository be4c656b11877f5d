"""Kimi Delta Attention: the gated delta rule with a decay for each key
channel, as a chunked Pallas kernel family (forward and backward) whose
running state stays in VMEM.

The recurrence, per head, with a ``[d_k, d_v]`` float32 state ``S_0 = 0``
(``q``, ``k`` here after the L2 norm, ``q`` also times ``d_k^-1/2``)::

    S'  = Diag(exp g_t) S_{t-1}                g_t <= 0, one a key channel
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

:func:`kda_attention` takes ``q``, ``k``, ``v``, ``g`` as ``[B, S, H * d]``
(heads on the lanes, as the projections and the convolution leave them:
a head's ``[C, d]`` tile is one lane-aligned block, nothing is transposed
at the door) and ``beta`` as ``[B, S, H]``. ``q`` and ``k`` arrive
UN-normalised (the convolution's output after SiLU): both kernels
normalise a tile in VMEM, so the convolution's output is the one copy of
each operand the backward keeps.

With ``conv=KdaConv(...)`` (the taps of a depthwise causal convolution
for each of q, k, v) the operands are the PROJECTIONS' outputs and the
convolution and SiLU are the kernels' as well: a grid step first stages
each operand's block in float32 behind the last rows of the block before
it (a second, 16-row block spec on the same operand; zeros in the
sequence's first block), reads the staged rows at the taps' static row
offsets, and writes ``SiLU(conv)`` to a VMEM scratch that the chunks read
where they read the operand; nothing convolved is live while a chunk is
worked and nothing convolved ever reaches HBM. The backward (blocks in
reverse) does the same, keeps SiLU's derivative beside it, stages the
gradients it forms of the convolved operands (times that derivative)
``[block + 8, d]`` with the FIRST rows of the block after it behind them
(left by the grid step before; zeros at the sequence's end), and reads
the convolution's transpose and the taps' gradients off that stage: ``dx_t
= sum_i w_i d_{t + n - 1 - i}``, ``dW_i = sum_t d_{t + n - 1 - i} x_t``
(the same shifted reads serve both). ``dW`` accumulates over a head's
blocks in one float32 ``[8, d]`` output block (rows >= n zero) that the
sequence axis revisits; XLA sums it over the batch under the scope
``kda_conv``. Same two kernels, one body each with a static branch: a
call without ``conv`` traces what it traced.

With ``out_norm=eps`` the result is each head's output RMS-normalised over
its ``d_v`` channels, ``o^ = o rsqrt(mean_d(o^2) + eps)`` (no scale: the
caller's), the statistic taken where the tile already lies: the forward
normalises a chunk's float32 ``[C, d_v]`` output in VMEM the moment before
it rounds and writes it (one rounding, of the normalised value), and
leaves ``rstd = 1 / rms``, float32, as a row vector a head (``[B, H, 1,
S_pad]``, ``dbeta``'s layout; the entry's glue hands it on as ``[B, S,
H]``). The backward takes ``do^`` where it took ``do`` and two more
operands, ``o^`` (the forward's output, the one copy kept) and ``rstd``
(read as ``beta`` is), forms ``do = rstd (do^ - o^ mean_d(do^ o^))`` in
float32 a chunk and goes on as without. No float32 ``[B, S, H, d]`` array
of the output is ever built around the kernels for a norm to reduce. One
more static branch of the same bodies: a call without ``out_norm`` traces
what it traced.

The chunked form (``C`` rows a chunk, ``G_i = sum_{j<=i} g_j`` inside the
chunk, ``S_0`` the state the chunk enters with; rows ``i``, ``j``)::

    P_kk[i, j] = sum_c k_ic k_jc exp(G_ic - G_jc)        j <  i
    P_qk[i, j] = sum_c q_ic k_jc exp(G_ic - G_jc)        j <= i
    A = Diag(beta) P_kk;  M = (I + A)^-1
    U = M Diag(beta) (V - (K * exp G) S_0)
    O = (Q * exp G) S_0 + P_qk U
    S_C = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U

**Every exponent the kernels form is <= 0.** The decay is per channel, so
``exp(G_i - G_j)`` does not factor out of the dot, and the cheap
factorisation ``(q exp G_i) . (k exp -G_j)`` overflows float32 within a
chunk at the strongest decays a Kimi-Linear layer is initialised with
(``g`` down to -1.6 a step). A chunk is cut into sub-blocks of ``sub``
rows. A pair of rows in the SAME sub-block is computed pairwise, one
column a step (``sub`` steps over the whole chunk: ``exp(min(G_i - G_j,
0))`` on the VPU, the sum over channels a lane reduction). A row ``i`` in
sub-block ``r`` and a column ``j`` in an earlier one are normalised at
``r``'s first row ``b``: ``(q_i exp(G_i - G_b)) . (k_j exp(G_b - G_j))``,
both exponents <= 0 for ``j < b <= i``, a plain matmul.

``(I + A)^-1`` of the unit lower triangle, in float32, in two levels, each
an exact finite product because its matrix is nilpotent: the diagonal
``sub x sub`` blocks at once as ``(I - A_d)(I + A_d^2)(I + A_d^4)...``,
then with ``N = M_d A_o`` (strictly block-lower, ``N^(C/sub) = 0``) ``M =
(I - N)(I + N^2)... M_d``. The powers of an ``8 x 8`` block stay small
where those of the whole ``64 x 64`` triangle (binomials up to ``C(63,
31)``) would cancel catastrophically on repeated keys. Each product is
three bfloat16 passes on operands split in two (an error of 2^-17, where
the inverse's own rounding to bfloat16 for the matmuls that use it is
2^-9), or one float32 product at full precision where the operands are
float32; the cumulated log-decays are a product with a 0 / 1 triangle,
exact in three passes.

The grid is ``(batch, head, blocks of chunks)``; a head's chunks run in
order and its state, held TRANSPOSED ``[d_v, d_k]`` so that the key
channels' decay multiplies along the lanes, lives in one float32 VMEM
scratch from the first chunk to the last. The forward writes each chunk's
ENTRY state to HBM (``[B, H, S/C, d_v, d_k]``, in the operands' dtype) as
the backward's residual, and nothing else of the state ever leaves VMEM;
the backward runs the chunks in reverse with ``dS`` in the scratch,
recomputes ``P``, ``M`` and ``U`` from the saved entry state, and hands
back ``dq``, ``dk``, ``dv`` (the operands' dtype, of the UN-normalised
``q`` / ``k``), ``dg`` and ``dbeta`` (float32).

Kernel names: ``hvd_kda_fwd``, ``hvd_kda_bwd``. The entry's own XLA glue
(padding, ``dbeta``'s and ``rstd``'s layout) is under the scope
``attn_layout``; the ``pallas_call``s are under none. Build-time counters
(always on):
``kda.calls`` (one a kernel built), ``kda.calls.conv`` (of those, the
kernels that convolve), ``kda.calls.out_norm`` (of those, the kernels
that normalise their exit), ``kda.chunks`` (chunks a forward call
visits: ``B H S_pad / C``), ``kda.state_bytes_saved`` (bytes of entry
states a forward call leaves for its backward).

**The scalar-gate form** (Gated DeltaNet). ``g`` of shape ``[B, S, H]``,
ONE log-decay a head and position, selects it; no flag does. ``exp(G_i -
G_j)`` is then one ``[C, C]`` matrix a head that multiplies ``Q K^T`` and
``K K^T`` whole, no sub-block is formed, and a head's widths need be no
whole 128-lane tiles: the section "The scalar-gate form" below has the
chunk, the grid and how the lanes are filled. The inverse, ``U``, the entry
states, ``conv`` and ``out_norm`` are shared. Kernel names ``hvd_gdn_fwd``,
``hvd_gdn_bwd``; counters ``gdn.*`` as ``kda.*`` above, and
``gdn.calls.lanes_padded`` (kernels built for heads that are no whole lane
tiles).

``use_kernel=False`` (the default off the TPU) is the recurrence itself, a
``lax.scan`` a position in float32 under ``jax.checkpoint`` a group of
positions: the differential of the tests and the CPU path of the model.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..context import device_platform
from ..obs import registry as _registry

__all__ = ["KdaConv", "kda_attention", "kda_recurrence"]

_VMEM = pltpu.VMEM
_GLUE_SCOPE = "attn_layout"
_HI = lax.Precision.HIGHEST
NORM_EPS = 1e-6  # of the L2 norm of q and k: x / sqrt(sum x^2 + eps)
_LOG2E = math.log2(math.e)

# The plan's statics on the chip: rows a chunk, rows a sub-block (8: one
# float32 sublane tile; at 16 the pairwise steps doubled and the compiled
# schedule was 13% longer), chunks a grid step (two: 128 rows, so that
# dbeta's row vector leaves as whole 128-lane tiles).
CHUNK = 64
SUB = 8
BLOCK_CHUNKS = 2
# With ``conv``: the rows of the block before that a grid step reads beside
# its own (one packed tile of a 16-bit operand; their last ``_EDGE`` are
# staged in front of the block's), and the rows a float32 tile has: what a
# block hands the block before it in the backward.
_HALO = 16
_EDGE = 8
_CONV_SCOPE = "kda_conv"


class KdaConv(NamedTuple):
    """The taps ``[n, H * d]`` float32 of the depthwise causal convolutions
    that :func:`kda_attention` does at its door, each followed by SiLU:
    ``y_t = SiLU(sum_i w_i x_{t - (n - 1) + i})``, zeros before row 0 (tap
    ``n - 1`` meets the position itself)."""
    q: jax.Array
    k: jax.Array
    v: jax.Array


class _Plan(NamedTuple):
    b: int
    s: int
    s_pad: int
    h: int
    dk: int
    dv: int
    chunk: int
    sub: int
    block: int  # rows a grid step: a whole number of chunks
    interpret: bool
    # of q, k, v, out, the saved states and the MXU's operands (float32:
    # every matmul at full precision)
    dtype: object
    taps: int = 0  # of the convolution the kernels do at their door; 0: none
    # eps of the head-wise RMS norm the kernels do at their exit; None: none
    out_norm: Optional[float] = None
    # the scalar-gate form (``g`` one a head): every head a grid step
    scalar: bool = False

    @property
    def n_chunks(self) -> int:
        return self.s_pad // self.chunk

    @property
    def state_bytes(self) -> int:
        return (self.b * self.h * self.n_chunks * self.dv * self.dk
                * jnp.dtype(self.dtype).itemsize)


def _plan(q, v, beta, *, n_heads: int, chunk: Optional[int],
          sub: Optional[int], interpret: Optional[bool],
          conv: Optional[KdaConv] = None,
          out_norm: Optional[float] = None, scalar: bool = False) -> _Plan:
    b, s, width = q.shape
    h = n_heads
    if width % h or v.shape[-1] % h or beta.shape != (b, s, h):
        raise ValueError(
            f"q {q.shape}, v {v.shape}, beta {beta.shape}: not {h} heads"
        )
    if interpret is None:
        interpret = device_platform() != "tpu"
    dk, dv = width // h, v.shape[-1] // h
    chunk = chunk or CHUNK
    sub = min(sub or SUB, chunk)
    if chunk % sub or sub & (sub - 1) or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(f"chunk {chunk} in sub-blocks of {sub}")
    # the scalar-gate form holds every head's lanes in one block as wide as
    # the operand and cuts a head out of it in VMEM: any width compiles
    if not interpret and not scalar and (dk % 128 or dv % 128 or chunk % 8):
        raise ValueError(
            f"compiled for the TPU a head is a whole number of 128-lane "
            f"tiles: d_k {dk}, d_v {dv}"
        )
    # a grid step's rows: whole chunks, and dbeta's row vector a whole
    # number of 128-lane tiles once the sequence is longer than one
    per_block = max(BLOCK_CHUNKS, -(-128 // chunk))
    block = chunk * min(per_block, -(-s // chunk))
    if block > 128 and block % 128:
        block = -(-block // 128) * 128
    taps = 0
    if conv is not None:
        taps = conv.q.shape[0]
        want = [(taps, width), (taps, width), (taps, v.shape[-1])]
        if [w.shape for w in conv] != want or not 1 <= taps <= _EDGE + 1:
            raise ValueError(
                f"conv taps {[w.shape for w in conv]}: not {want} with 1 "
                f"to {_EDGE + 1} taps"
            )
        if block % _HALO:
            raise ValueError(
                f"with conv a grid step's rows ({block}: chunk {chunk}) "
                f"are a multiple of {_HALO}"
            )
    return _Plan(
        b, s, -(-s // block) * block, h, dk, dv, chunk, sub, block,
        interpret, q.dtype, taps,
        None if out_norm is None else float(out_norm), scalar,
    )


# ---------------------------------------------------------------------------
# Matmuls. ``dt`` is the operands' dtype on the MXU: the inputs' (bfloat16
# in a model, accumulated in float32), or float32 at full precision where
# the inputs are float32 (tests against the recurrence).
# ---------------------------------------------------------------------------


def _dot(a, b, dims, dt):
    exact = dt == jnp.float32
    return lax.dot_general(
        a.astype(dt), b.astype(dt), (dims, ((), ())),
        precision=_HI if exact else None,
        preferred_element_type=jnp.float32,
    )


def _nn(a, b, dt=jnp.float32):  # [m, k] [k, n]
    return _dot(a, b, ((1,), (0,)), dt)


def _nt(a, b, dt=jnp.float32):  # [m, k] [n, k]
    return _dot(a, b, ((1,), (1,)), dt)


def _tn(a, b, dt=jnp.float32):  # [k, m] [k, n]
    return _dot(a, b, ((0,), (0,)), dt)


def _over_sub(x, j: int, sub: int):
    """Row ``j`` of each sub-block of ``x [C, d]``, repeated over the
    sub-block's rows."""
    c, d = x.shape
    tiles = x.reshape(c // sub, sub, d)
    return jnp.broadcast_to(tiles[:, j:j + 1, :], tiles.shape).reshape(c, d)


def _sum_sub(x, sub: int):
    """The sum over each sub-block's rows of ``x [C, d]``, repeated over
    them."""
    c, d = x.shape
    tiles = x.reshape(c // sub, sub, d)
    return jnp.broadcast_to(
        tiles.sum(axis=1, keepdims=True), tiles.shape
    ).reshape(c, d)


def _l2(x, eps: float, scale: float):
    """``x / sqrt(sum x^2 + eps) * scale`` over the lanes, and the factor
    ``1 / sqrt(...)`` (``[C, 1]``) its backward needs."""
    r = lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
    return x * (r * scale), r


def _l2_bwd(x, r, scale: float, g):
    """The gradient of ``x`` given that of ``_l2(x)``."""
    return (r * scale) * (g - x * (r * r * jnp.sum(x * g, -1, keepdims=True)))


class _Chunk(NamedTuple):
    """What both passes compute of one chunk before they meet the state."""
    q: jax.Array  # normalised, scaled [C, dk]
    k: jax.Array  # normalised [C, dk]
    rq: jax.Array  # [C, 1] the norms' factors
    rk: jax.Array
    big_g: jax.Array  # G log2(e): the log-decays cumulated inside the chunk
    e: jax.Array  # exp G
    e_end: jax.Array  # exp(G_C - G): a row's decay to the chunk's end
    e_last: jax.Array  # [1, dk] exp G_C
    to_start: jax.Array  # exp(G - G_b), b the first row of the row's sub-block
    from_start: list  # r -> exp(min(G_b(r) - G, 0)), b(r) sub-block r's first
    p_kk: jax.Array  # [C, C] strictly below the diagonal
    p_qk: jax.Array  # [C, C] on and below it
    inv: jax.Array  # (I + Diag(beta) P_kk)^-1, float32


def _masks(c: int, sub: int):
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row_sub, col_sub = row & -sub, col & -sub  # first row of the sub-block
    return row, col, row_sub, col_sub


def _split(x):
    """``x`` as two bfloat16 terms, ``x ~ hi + lo`` to 2^-17 of it."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _nn_split(a, b):
    """``a b`` in float32 from three bfloat16 passes (``hi hi + hi lo + lo
    hi``): half the MXU passes of a full-precision float32 product, an
    error of 2^-17 where the operands' own rounding to bfloat16, which
    follows wherever the result is used, is 2^-9."""
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    dot = lambda x, y: _nn(x, y, jnp.bfloat16)  # noqa: E731
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def _sum_over(ones, x):
    """``ones x`` for a 0 / 1 matrix ``ones`` and float32 ``x [C, d]``,
    exact to float32: ``x`` as three bfloat16 terms side by side, one pass
    (the 0 / 1 matrix is exact in bfloat16, so the other passes of a
    full-precision product would add nothing)."""
    d = x.shape[1]
    hi = x.astype(jnp.bfloat16)
    mid, low = _split(x - hi.astype(jnp.float32))
    out = _nn(ones.astype(jnp.bfloat16),
              jnp.concatenate([hi, mid, low], axis=1), jnp.bfloat16)
    return out[:, :d] + (out[:, d:2 * d] + out[:, 2 * d:])


def _inverse(a, sub: int, exact: bool):
    """``(I + a)^-1`` for ``a [C, C]`` strictly lower triangular, in
    float32 (``exact``: every product at full precision; else three
    bfloat16 passes a product): the diagonal ``sub x sub`` blocks' inverses
    as one finite product, then the blocks joined by a second one."""
    c = a.shape[0]
    nn = _nn if exact else _nn_split
    row, col, row_sub, col_sub = _masks(c, sub)
    eye = (row == col).astype(jnp.float32)
    a_diag = jnp.where(row_sub == col_sub, a, 0.0)
    inv_diag, power = eye - a_diag, a_diag
    for _ in range(int(math.log2(sub)) - 1):
        power = nn(power, power)
        inv_diag = nn(inv_diag, eye + power)
    if c == sub:
        return inv_diag
    n = nn(inv_diag, a - a_diag)  # strictly block-lower
    inv_off, power = eye - n, n
    for _ in range(int(math.log2(c // sub)) - 1):
        power = nn(power, power)
        inv_off = nn(inv_off, eye + power)
    return nn(inv_off, inv_diag)


def _chunk(q_raw, k_raw, g, beta, *, sub: int, dt) -> _Chunk:
    """``q_raw``, ``k_raw``, ``g`` float32 ``[C, dk]``, ``beta [C, 1]``."""
    c, dk = k_raw.shape
    q, rq = _l2(q_raw, NORM_EPS, dk ** -0.5)
    k, rk = _l2(k_raw, NORM_EPS, 1.0)
    row, col, row_sub, col_sub = _masks(c, sub)
    # inclusive cumsum, in units of log 2: every decay below is an exp2
    big_g = _sum_over(col <= row, g) * _LOG2E
    g_last = big_g[c - 1:c, :]
    to_start = jnp.exp2(big_g - _over_sub(big_g, 0, sub))
    left = jnp.concatenate([k * to_start, q * to_start], axis=0)  # [2C, dk]

    # a row against the columns of EARLIER sub-blocks: matmuls
    off_kk = jnp.zeros((c, c), jnp.float32)
    off_qk = jnp.zeros((c, c), jnp.float32)
    from_start = [None]
    for r in range(1, c // sub):
        first = big_g[r * sub:r * sub + 1, :]
        from_start.append(jnp.exp2(jnp.minimum(first - big_g, 0.0)))
        both = _nt(left, k * from_start[r], dt)  # [2C, C]
        mine = row_sub == r * sub
        off_kk = jnp.where(mine, both[:c], off_kk)
        off_qk = jnp.where(mine, both[c:], off_qk)

    # a row against the columns of its OWN sub-block: pairwise, one column
    # of every sub-block a step
    own_kk = jnp.zeros((c, c), jnp.float32)
    own_qk = jnp.zeros((c, c), jnp.float32)
    for j in range(sub):
        decay = jnp.exp2(jnp.minimum(big_g - _over_sub(big_g, j, sub), 0.0))
        kj = _over_sub(k, j, sub) * decay
        hit = col - row_sub == j
        own_kk = jnp.where(
            hit, jnp.sum(k * kj, axis=-1, keepdims=True), own_kk
        )
        own_qk = jnp.where(
            hit, jnp.sum(q * kj, axis=-1, keepdims=True), own_qk
        )

    earlier, own = col_sub < row_sub, col_sub == row_sub
    p_kk = jnp.where(earlier, off_kk,
                     jnp.where(own & (col < row), own_kk, 0.0))
    p_qk = jnp.where(earlier, off_qk,
                     jnp.where(own & (col <= row), own_qk, 0.0))
    return _Chunk(
        q, k, rq, rk, big_g, jnp.exp2(big_g), jnp.exp2(g_last - big_g),
        jnp.exp2(g_last), to_start, from_start, p_kk, p_qk,
        _inverse(beta * p_kk, sub, dt == jnp.float32),
    )


def _head_column(block, head):
    """Column ``head`` of ``block [C, H]`` as ``[C, 1]``."""
    lanes = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lanes == head, block, 0.0), -1, keepdims=True)


def _as_row(column):
    """``[n, 1] -> [1, n]`` by the diagonal of its broadcast."""
    n = column.shape[0]
    row, col, _, _ = _masks(n, 1)
    return jnp.sum(jnp.where(row == col, column, 0.0), 0, keepdims=True)


def _write_as_rows(ref, columns, p: _Plan):
    """The chunks' ``[C, 1]`` columns (``columns[i]`` chunk ``i``'s) leave
    as a row vector, rows on the lanes, a 128-lane tile (or the whole short
    block) at a time."""
    piece = min(p.block, 128)
    for first in range(0, p.block, piece):
        column = jnp.concatenate(
            [columns[i]
             for i in range(first // p.chunk, (first + piece) // p.chunk)],
            axis=0,
        )
        ref[0, 0, :, first:first + piece] = _as_row(column)


# ---------------------------------------------------------------------------
# The convolution at the door (``conv=``). A grid step stages each operand's
# block in float32 behind the last ``_EDGE`` rows of the block before it
# (zeros in the sequence's first block), convolves and gates it into a VMEM
# scratch BEFORE the chunks are worked, so that nothing of it is live in
# them; the chunks read that scratch where they read the operand's block.
# ---------------------------------------------------------------------------


def _convolve(x_ref, halo_ref, taps_ref, stage, out, slope, at_start):
    """One operand at the door: stages ``[the halo's last rows | the
    block]`` in ``stage [_EDGE + block, d]``, float32, and writes ``SiLU``
    of the block's convolution to ``out [block, d]`` (and SiLU's derivative
    there to ``slope``, where the backward brings one). ``at_start``: this
    is the sequence's first block, whose halo is zeros."""
    n = taps_ref.shape[0]
    halo = halo_ref[0, _HALO - _EDGE:, :].astype(jnp.float32)
    stage[:_EDGE, :] = jnp.where(at_start, 0.0, halo)
    stage[_EDGE:, :] = x_ref[0].astype(jnp.float32)
    c = sum(
        taps_ref[i:i + 1, :]
        * stage[pl.ds(_EDGE - (n - 1 - i), out.shape[0]), :]
        for i in range(n)
    )
    gate = jax.nn.sigmoid(c)
    out[...] = c * gate
    if slope is not None:
        slope[...] = gate * (1.0 + c * (1.0 - gate))


def _door_backward(p: _Plan, taps, stages, ahead, dtaps, d_refs):
    """The convolution's transpose, at the end of a backward grid step: a
    row's gradient reaches the rows up to ``n - 1`` BEFORE it, so the
    block's first rows wait in ``ahead`` for the block before, which the
    next grid step works. ``dtaps[x]``'s block is ``[..., _EDGE, d]`` with
    unit axes in front."""
    for x, d_ref in enumerate(d_refs):
        grads, d_taps = ahead[x], dtaps[x]
        lead = (0,) * (len(d_taps.shape) - 2)
        mine = stages[x][_EDGE:, :]  # the block's own rows, float32
        tap = lax.broadcasted_iota(jnp.int32, d_taps.shape[-2:], 0)
        dx = jnp.zeros_like(mine)
        dw = jnp.zeros(d_taps.shape[-2:], jnp.float32)
        for i in range(p.taps):
            later = grads[pl.ds(p.taps - 1 - i, p.block), :]
            dx = dx + taps[x][i:i + 1, :] * later
            dw = dw + jnp.where(
                tap == i, jnp.sum(later * mine, axis=0, keepdims=True),
                0.0,
            )
        d_ref[0] = dx.astype(d_ref.dtype)
        d_taps[lead] += dw
        grads[p.block:, :] = grads[:_EDGE, :]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, p: _Plan):
    c = p.chunk
    dt = p.dtype
    own = (q_ref, k_ref, v_ref)
    rest = list(rest)
    halos, taps = (rest.pop(0), rest.pop(0)) if p.taps else (None, None)
    o_ref, states_ref = rest.pop(0), rest.pop(0)
    rstd_ref = rest.pop(0) if p.out_norm is not None else None
    if p.taps:
        state, stages, convolved = rest
        at_start = pl.program_id(2) == 0
        for x in range(3):
            _convolve(own[x], halos[x], taps[x], stages[x], convolved[x],
                      None, at_start)
        read = lambda x, rows: convolved[x][rows, :]  # noqa: E731
    else:
        state, = rest
        read = lambda x, rows: own[x][0, rows, :].astype(  # noqa: E731
            jnp.float32
        )

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    head = pl.program_id(1)
    rstd = {}
    for i in range(p.block // c):
        rows = slice(i * c, (i + 1) * c)
        beta = _head_column(beta_ref[0, rows, :], head)
        ch = _chunk(read(0, rows), read(1, rows), g_ref[0, rows, :], beta,
                    sub=p.sub, dt=dt)
        # U = u_hat - w S_0: both through the inverse before S_0 is read
        w = _nn(ch.inv, beta * (ch.k * ch.e), dt)
        u_hat = _nn(ch.inv, beta * read(2, rows), dt)
        s0 = state[...]  # [dv, dk]
        states_ref[0, 0, i] = s0.astype(states_ref.dtype)
        u = u_hat - _nt(w, s0, dt)
        o = _nt(ch.q * ch.e, s0, dt) + _nn(ch.p_qk, u, dt)
        if p.out_norm is not None:  # the float32 tile, before its rounding
            rstd[i] = lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + p.out_norm
            )
            o = o * rstd[i]
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        state[...] = s0 * ch.e_last + _tn(u, ch.k * ch.e_end, dt)
    if p.out_norm is not None:
        _write_as_rows(rstd_ref, rstd, p)


def _specs(p: _Plan, block_of):
    """Block specs of the operands every kernel of the family shares, for
    a grid ``(batch, head, block)``; ``block_of(i)`` the sequence block a
    grid step works on."""
    wide = lambda d: pl.BlockSpec(  # noqa: E731
        (1, p.block, d), lambda bi, hi, i: (bi, block_of(i), hi),
        memory_space=_VMEM,
    )
    beta = pl.BlockSpec(
        (1, p.block, p.h), lambda bi, hi, i: (bi, block_of(i), 0),
        memory_space=_VMEM,
    )
    states = pl.BlockSpec(
        (1, 1, p.block // p.chunk, p.dv, p.dk),
        lambda bi, hi, i: (bi, hi, block_of(i), 0, 0), memory_space=_VMEM,
    )
    return wide, beta, states


def _door_specs(p: _Plan, block_of):
    """With ``conv``: the specs of q, k and v's halos (the ``_HALO`` rows
    that end where the grid step's block starts; the first block reads its
    own first rows and masks them) and of their taps (a head's ``[n, d]``)."""
    per_block = p.block // _HALO
    halo = lambda d: pl.BlockSpec(  # noqa: E731
        (1, _HALO, d),
        lambda bi, hi, i: (
            bi, jnp.maximum(block_of(i) * per_block - 1, 0), hi
        ),
        memory_space=_VMEM,
    )
    taps = lambda d: pl.BlockSpec(  # noqa: E731
        (p.taps, d), lambda bi, hi, i: (0, hi), memory_space=_VMEM
    )
    widths = (p.dk, p.dk, p.dv)
    return [tuple(halo(d) for d in widths), tuple(taps(d) for d in widths)]


def _rows(p: _Plan, block_of):
    """The spec and shape of an output that holds one float32 a row and
    head as row vectors, ``[B, H, 1, S_pad]`` (``dbeta``, ``rstd``)."""
    spec = pl.BlockSpec(
        (1, 1, 1, p.block), lambda bi, hi, i: (bi, hi, 0, block_of(i)),
        memory_space=_VMEM,
    )
    return spec, jax.ShapeDtypeStruct((p.b, p.h, 1, p.s_pad), jnp.float32)


def _from_rows(x, p: _Plan):
    """``[B, H, 1, S_pad] -> [B, S, H]``."""
    return jnp.swapaxes(x[:, :, 0, :p.s], 1, 2)


def _tiles(p: _Plan, rows: int):
    """A float32 ``[rows, d]`` VMEM scratch for each of q, k and v."""
    return tuple(_VMEM((rows, d), jnp.float32) for d in (p.dk, p.dk, p.dv))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 << 20,
    )


def _pad(x, p: _Plan):
    if p.s_pad == p.s:
        return x
    # zeros: beta 0 writes nothing to the state and g 0 decays nothing
    return jnp.pad(x, ((0, 0), (0, p.s_pad - p.s), (0, 0)))


def _book(p: _Plan, forward: bool) -> None:
    # each form books its own counters, under literal names
    # (``tools/check_metric_names.py`` reads them off the source)
    reg = _registry.always()
    if p.scalar:
        reg.counter("gdn.calls").inc()
        if p.taps:
            reg.counter("gdn.calls.conv").inc()
        if p.out_norm is not None:
            reg.counter("gdn.calls.out_norm").inc()
        if p.dk % 128 or p.dv % 128:
            # a head's lanes are cut out of the operand-wide block and
            # padded to whole 128-lane tiles in VMEM (the one lane filling
            # that form has)
            reg.counter("gdn.calls.lanes_padded").inc()
        if forward:
            reg.counter("gdn.chunks").inc(p.b * p.h * p.n_chunks)
            reg.counter("gdn.state_bytes_saved").inc(p.state_bytes)
        return
    reg.counter("kda.calls").inc()
    if p.taps:
        reg.counter("kda.calls.conv").inc()
    if p.out_norm is not None:
        reg.counter("kda.calls.out_norm").inc()
    if forward:
        reg.counter("kda.chunks").inc(p.b * p.h * p.n_chunks)
        reg.counter("kda.state_bytes_saved").inc(p.state_bytes)


@functools.partial(jax.jit, static_argnames=("p",), inline=True)
def _fwd_call(q, k, v, g, beta, conv=None, *, p: _Plan):
    with jax.named_scope(_GLUE_SCOPE):
        q, k, v, g, beta = (_pad(x, p) for x in (q, k, v, g, beta))
    wide, beta_spec, states_spec = _specs(p, lambda i: i)
    in_specs = [wide(p.dk), wide(p.dk), wide(p.dv), wide(p.dk), beta_spec]
    operands = [q, k, v, g, beta]
    scratch = [_VMEM((p.dv, p.dk), jnp.float32)]
    if p.taps:
        in_specs += _door_specs(p, lambda i: i)
        operands += [(q, k, v), tuple(conv)]
        scratch += [_tiles(p, _EDGE + p.block), _tiles(p, p.block)]
    out_specs = [wide(p.dv), states_spec]
    out_shape = [
        jax.ShapeDtypeStruct((p.b, p.s_pad, p.h * p.dv), p.dtype),
        jax.ShapeDtypeStruct((p.b, p.h, p.n_chunks, p.dv, p.dk), p.dtype),
    ]
    if p.out_norm is not None:
        row_spec, row_shape = _rows(p, lambda i: i)
        out_specs.append(row_spec)
        out_shape.append(row_shape)
    out, states, *rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(p.b, p.h, p.s_pad // p.block),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_params(),
        interpret=p.interpret,
        name="hvd_kda_fwd",
    )(*operands)
    with jax.named_scope(_GLUE_SCOPE):
        if rstd:
            return out[:, :p.s], states, _from_rows(rstd[0], p)
        return out[:, :p.s], states


# ---------------------------------------------------------------------------
# Backward: the chunks in reverse, dS (transposed like S) in the scratch.
# With Z = V - (K e) S_0, R = beta Z, U = M R, and dO, dS_C given:
#
#     dU  = P_qk^T dO + (K e_end) dS_C          dR = M^T dU,  dZ = beta dR
#     dP_qk = tril(dO U^T)                      dP_kk = -tril_strict(dZ U^T)
#     dbeta = rowsum(dR Z) - rowsum(tril_strict(dR U^T) P_kk)
#     dV = dZ;  d(K e) = -dZ S_0^T;  d(Q e) = dO S_0^T;  d(K e_end) = U dS_C^T
#     dS_0 = (Q e)^T dO - (K e)^T dZ + Diag(e_last) dS_C
#
# The pairwise terms hand their gradients to q (``dq_pair``), to k as the
# row (``dk_row``) and to k as the column (``dk_col``) through the same
# two safe forms as the forward, and to G as ``q dq_pair + k (dk_row -
# dk_col)``; ``dg`` is dG cumulated from the chunk's end.
# ---------------------------------------------------------------------------


def _pair_backward(ch: _Chunk, dp_kk, dp_qk, dp_kk_t, dp_qk_t, *, sub, dt):
    """``dp_*`` as ``[row, col]`` and ``dp_*_t`` as ``[col, row]``, each
    already masked to its triangle. Returns ``dq_pair``, ``dk_row``,
    ``dk_col`` ``[C, dk]`` (of the normalised q and k)."""
    c, dk = ch.k.shape
    row, col, row_sub, col_sub = _masks(c, sub)
    left = jnp.concatenate([ch.k * ch.to_start, ch.q * ch.to_start], axis=0)
    rows_out = jnp.zeros((2 * c, dk), jnp.float32)
    dk_col = jnp.zeros((c, dk), jnp.float32)
    for r in range(1, c // sub):
        scale = ch.from_start[r]
        # [row, col]: rows of sub-block r, columns of earlier ones
        mine = (row_sub == r * sub) & (col_sub < row_sub)
        stacked = jnp.concatenate([
            jnp.where(mine, dp_kk, 0.0), jnp.where(mine, dp_qk, 0.0)
        ], axis=0)  # [2C, C]
        rows_out = rows_out + _nn(stacked, ch.k * scale, dt)
        # [col, row]: the same entries, turned
        mine_t = (col_sub == r * sub) & (row_sub < col_sub)
        stacked_t = jnp.concatenate([
            jnp.where(mine_t, dp_kk_t, 0.0), jnp.where(mine_t, dp_qk_t, 0.0)
        ], axis=1)  # [C, 2C]
        dk_col = dk_col + scale * _nn(stacked_t, left, dt)
    dk_row = rows_out[:c] * ch.to_start
    dq_pair = rows_out[c:] * ch.to_start

    in_sub = lax.broadcasted_iota(jnp.int32, (c, dk), 0) & (sub - 1)
    for j in range(sub):
        decay = jnp.exp2(
            jnp.minimum(ch.big_g - _over_sub(ch.big_g, j, sub), 0.0)
        )
        kj = _over_sub(ch.k, j, sub) * decay
        hit = col - row_sub == j
        c_kk = jnp.sum(jnp.where(hit, dp_kk, 0.0), axis=-1, keepdims=True)
        c_qk = jnp.sum(jnp.where(hit, dp_qk, 0.0), axis=-1, keepdims=True)
        dk_row = dk_row + c_kk * kj
        dq_pair = dq_pair + c_qk * kj
        dk_col = dk_col + jnp.where(
            in_sub == j,
            _sum_sub((c_kk * ch.k + c_qk * ch.q) * decay, sub), 0.0,
        )
    return dq_pair, dk_row, dk_col


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                *rest, p: _Plan):
    c = p.chunk
    dt = p.dtype
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    own = (q_ref, k_ref, v_ref)
    rest = list(rest)
    if p.out_norm is not None:  # the forward's normalised output and 1 / rms
        out_ref, rstd_ref = rest.pop(0), rest.pop(0)
    if p.taps:
        (halos, taps, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dtaps,
         dstate, stages, convolved, slopes, ahead) = rest
        at_start = pl.program_id(2) == p.s_pad // p.block - 1
        for x in range(3):
            _convolve(own[x], halos[x], taps[x], stages[x], convolved[x],
                      slopes[x], at_start)
        read = lambda x, rows: convolved[x][rows, :]  # noqa: E731

        def write(x, rows, grad):  # before the SiLU, float32, staged
            ahead[x][rows, :] = grad * slopes[x][rows, :]

        @pl.when(pl.program_id(2) == 0)
        def _():
            for x in range(3):
                ahead[x][p.block:, :] = jnp.zeros_like(ahead[x][p.block:, :])
                dtaps[x][...] = jnp.zeros_like(dtaps[x])
    else:
        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate = rest
        read = lambda x, rows: f32(own[x][0, rows, :])  # noqa: E731

        def write(x, rows, grad):
            ref = (dq_ref, dk_ref, dv_ref)[x]
            ref[0, rows, :] = grad.astype(ref.dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    head = pl.program_id(1)
    row, col, _, _ = _masks(c, p.sub)
    dbeta = {}
    for i in reversed(range(p.block // c)):
        rows = slice(i * c, (i + 1) * c)
        q_raw, k_raw = read(0, rows), read(1, rows)
        beta = _head_column(beta_ref[0, rows, :], head)
        ch = _chunk(q_raw, k_raw, g_ref[0, rows, :], beta, sub=p.sub, dt=dt)
        s0 = f32(states_ref[0, 0, i])  # [dv, dk]
        z = read(2, rows) - _nt(ch.k * ch.e, s0, dt)
        u = _nn(ch.inv, beta * z, dt)
        ds = dstate[...]  # [dv, dk], of the state this chunk leaves
        do = f32(do_ref[0, rows, :])
        if p.out_norm is not None:  # of o, given that of o rstd
            o_hat = f32(out_ref[0, rows, :])
            do = _head_column(rstd_ref[0, rows, :], head) * (
                do - o_hat * jnp.mean(do * o_hat, axis=-1, keepdims=True)
            )
        k_end = ch.k * ch.e_end
        du = _tn(ch.p_qk, do, dt) + _nt(k_end, ds, dt)
        dr = _tn(ch.inv, du, dt)
        dz = beta * dr
        # [dZ; dO; dR] U^T and U [dZ; dO]^T: the pairwise terms' gradients
        # as [row, col] and as [col, row], no transpose in the kernel
        by_row = _nt(jnp.concatenate([dz, do, dr], axis=0), u, dt)  # [3C, C]
        by_col = _nt(u, jnp.concatenate([dz, do], axis=0), dt)  # [C, 2C]
        below, above = col < row, col > row
        dp_kk = jnp.where(below, -by_row[:c], 0.0)
        dp_qk = jnp.where(col <= row, by_row[c:2 * c], 0.0)
        dp_kk_t = jnp.where(above, -by_col[:, :c], 0.0)
        dp_qk_t = jnp.where(col >= row, by_col[:, c:], 0.0)
        dbeta[i] = (
            jnp.sum(dr * z, axis=-1, keepdims=True)
            - jnp.sum(jnp.where(below, by_row[2 * c:], 0.0) * ch.p_kk,
                      axis=-1, keepdims=True)
        )
        dq_pair, dk_row, dk_col = _pair_backward(
            ch, dp_kk, dp_qk, dp_kk_t, dp_qk_t, sub=p.sub, dt=dt
        )
        d_kg = -_nn(dz, s0, dt)  # of K e
        d_qg = _nn(do, s0, dt)  # of Q e
        d_kend = _nn(u, ds, dt)  # of K e_end
        dq = d_qg * ch.e + dq_pair
        dk = d_kg * ch.e + d_kend * ch.e_end + dk_row + dk_col
        d_big_g = (
            d_qg * (ch.q * ch.e) + d_kg * (ch.k * ch.e) - d_kend * k_end
            + ch.q * dq_pair + ch.k * (dk_row - dk_col)
        )
        # G_C, the last row's: every row's decay to the chunk's end and
        # the entry state's own
        to_last = (
            jnp.sum(d_kend * k_end, axis=0, keepdims=True)
            + ch.e_last * jnp.sum(s0 * ds, axis=0, keepdims=True)
        )
        d_big_g = d_big_g + jnp.where(
            lax.broadcasted_iota(jnp.int32, d_big_g.shape, 0) == c - 1,
            to_last, 0.0,
        )
        dg_ref[0, rows, :] = _sum_over(col >= row, d_big_g)
        write(0, rows, _l2_bwd(q_raw, ch.rq, p.dk ** -0.5, dq))
        write(1, rows, _l2_bwd(k_raw, ch.rk, 1.0, dk))
        write(2, rows, dz)
        dstate[...] = ds * ch.e_last + _tn(
            jnp.concatenate([do, -dz], axis=0),
            jnp.concatenate([ch.q * ch.e, ch.k * ch.e], axis=0), dt,
        )

    _write_as_rows(dbeta_ref, dbeta, p)

    if p.taps:
        _door_backward(p, taps, stages, ahead, dtaps,
                       (dq_ref, dk_ref, dv_ref))


@functools.partial(jax.jit, static_argnames=("p",), inline=True)
def _bwd_call(q, k, v, g, beta, states, d_out, conv=None, normed=None, *,
              p: _Plan):
    with jax.named_scope(_GLUE_SCOPE):
        q, k, v, g, beta, d_out = (
            _pad(x, p) for x in (q, k, v, g, beta, d_out)
        )
    last = p.s_pad // p.block - 1
    wide, beta_spec, states_spec = _specs(p, lambda i: last - i)
    like = lambda x, dtype: jax.ShapeDtypeStruct(x.shape, dtype)  # noqa: E731
    in_specs = [wide(p.dk), wide(p.dk), wide(p.dv), wide(p.dk), beta_spec,
                states_spec, wide(p.dv)]
    operands = [q, k, v, g, beta, states, d_out]
    dbeta_spec, dbeta_shape = _rows(p, lambda i: last - i)
    out_specs = [wide(p.dk), wide(p.dk), wide(p.dv), wide(p.dk), dbeta_spec]
    out_shape = [
        like(q, p.dtype), like(k, p.dtype), like(v, p.dtype),
        like(g, jnp.float32), dbeta_shape,
    ]
    scratch = [_VMEM((p.dv, p.dk), jnp.float32)]
    if p.out_norm is not None:
        # the forward's output and its rows' 1 / rms ([B, S, H], as beta):
        # zeros in the padding, where d_out's zeros then stay zeros
        with jax.named_scope(_GLUE_SCOPE):
            operands += [_pad(x, p) for x in normed]
        in_specs += [wide(p.dv), beta_spec]
    if p.taps:
        in_specs += _door_specs(p, lambda i: last - i)
        operands += [(q, k, v), tuple(conv)]
        # the taps' gradients: a head's partial sums, one block that the
        # sequence axis revisits, rows >= n zero
        out_specs.append(tuple(
            pl.BlockSpec((1, 1, _EDGE, d), lambda bi, hi, i: (bi, hi, 0, 0),
                         memory_space=_VMEM)
            for d in (p.dk, p.dk, p.dv)
        ))
        out_shape.append(tuple(
            jax.ShapeDtypeStruct((p.b, p.h, _EDGE, d), jnp.float32)
            for d in (p.dk, p.dk, p.dv)
        ))
        # the staged blocks, the convolved ones, SiLU's derivative there,
        # the gradients before the SiLU with the block after's first rows
        scratch += [_tiles(p, _EDGE + p.block), _tiles(p, p.block),
                    _tiles(p, p.block), _tiles(p, p.block + _EDGE)]
    dq, dk, dv, dg, dbeta, *d_taps = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(p.b, p.h, p.s_pad // p.block),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_params(),
        interpret=p.interpret,
        name="hvd_kda_bwd",
    )(*operands)
    with jax.named_scope(_GLUE_SCOPE):
        dbeta = _from_rows(dbeta, p)
        grads = (dq[:, :p.s], dk[:, :p.s], dv[:, :p.s], dg[:, :p.s], dbeta)
    if not p.taps:
        return grads, None
    with jax.named_scope(_CONV_SCOPE):
        # [B, H, _EDGE, d] partial sums -> [n, H d], summed over the batch
        return grads, KdaConv(*(
            jnp.moveaxis(x.sum(axis=0)[:, :p.taps], 0, 1).reshape(p.taps, -1)
            for x in d_taps[0]
        ))


# ---------------------------------------------------------------------------
# The scalar-gate form (Gated DeltaNet): ``g`` is ONE log-decay a head and
# position, so the decay between two rows of a chunk is one ``[C, C]``
# matrix a head, ``D[i, j] = exp(G_i - G_j)`` (``j <= i``: every exponent
# <= 0), that multiplies ``Q K^T`` and ``K K^T`` whole:
#
#     P_kk = tril_strict(K K^T * D)      P_qk = tril(Q K^T * D)
#
# and ``e``, ``e_end``, ``e_last`` are a number a row where the
# channel-wise form has one a row and key channel. No sub-block is formed
# and nothing is computed pairwise on the VPU. Everything else is the
# family's: the inverse, ``U``, the entry states kept, the door and the
# exit.
#
# Lanes. A head's key and value widths need be no multiple of 128 (96 and
# 192 in the configuration that brought the form), so no block spec can cut
# a head out of ``[B, S, H d]``. A grid step therefore takes a block of
# rows as wide as the OPERAND (every head's lanes, as HBM holds them, no
# padding there) and works the heads one after another, each head's tile a
# static lane slice of the block in VMEM, which Mosaic pads to whole 128-lane
# tiles there and only there; the grid is ``(batch, 1, blocks)`` (the
# channel-wise form's with ONE "head" as wide as the operand, so the block
# specs, the door's and the scratches are shared: ``_whole_width``) and the
# scratch holds all ``H`` states ``[H, d_v, d_k]``. ``g``, ``beta``, ``dg``,
# ``dbeta`` and ``rstd`` are ``[B, S, H]`` blocks as they stand: a head's
# column is read by a lane mask and written into the ``[C, H]`` tile the
# same way, so nothing leaves as a row vector and the entry has no layout
# glue but the padding of the sequence. With ``conv`` the door is worked
# once a grid step over the block's whole width (lane-dense, elementwise).
# ---------------------------------------------------------------------------


def _chunk_scalar(q_raw, k_raw, g, beta, *, sub: int, dt):
    """``q_raw``, ``k_raw`` float32 ``[C, dk]``; ``g``, ``beta`` ``[C, 1]``.
    Returns a :class:`_Chunk` whose decays are ``[C, 1]`` (``e_last [1,
    1]``), and the decay matrix ``[C, C]`` as ``[row, col]`` and turned."""
    c, dk = k_raw.shape
    q, rq = _l2(q_raw, NORM_EPS, dk ** -0.5)
    k, rk = _l2(k_raw, NORM_EPS, 1.0)
    row, col, _, _ = _masks(c, 1)
    # inclusive cumsum down the rows, float32 on the VPU
    big_g = jnp.sum(jnp.where(col <= row, _as_row(g), 0.0), -1, keepdims=True)
    along = _as_row(big_g)  # [1, C]: G_j on the lanes
    g_last = big_g[c - 1:c, :]
    decay = jnp.exp(jnp.minimum(big_g - along, 0.0))  # [i, j]: exp(G_i - G_j)
    decay_t = jnp.exp(jnp.minimum(along - big_g, 0.0))  # [j, i]: the same
    both = _nt(jnp.concatenate([k, q], axis=0), k, dt)  # [2C, C]
    p_kk = jnp.where(col < row, both[:c] * decay, 0.0)
    p_qk = jnp.where(col <= row, both[c:] * decay, 0.0)
    ch = _Chunk(
        q, k, rq, rk, big_g, jnp.exp(big_g), jnp.exp(g_last - big_g),
        jnp.exp(g_last), None, None, p_kk, p_qk,
        _inverse(beta * p_kk, min(sub, c), dt == jnp.float32),
    )
    return ch, decay, decay_t


def _set_column(tile, head: int, column):
    """``tile [C, H]`` with column ``head`` set to ``column [C, 1]``."""
    lanes = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.where(lanes == head, column, tile)


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, p: _Plan):
    c, dt = p.chunk, p.dtype
    own = (q_ref, k_ref, v_ref)
    rest = list(rest)
    halos, taps = (rest.pop(0), rest.pop(0)) if p.taps else (None, None)
    o_ref, states_ref = rest.pop(0), rest.pop(0)
    rstd_ref = rest.pop(0) if p.out_norm is not None else None
    if p.taps:
        state, stages, convolved = rest
        at_start = pl.program_id(2) == 0
        for x in range(3):
            _convolve(own[x], halos[x], taps[x], stages[x], convolved[x],
                      None, at_start)
        read = lambda x, rows, lanes: convolved[x][rows, lanes]  # noqa: E731
    else:
        state, = rest
        read = lambda x, rows, lanes: own[x][0, rows, lanes].astype(  # noqa: E731
            jnp.float32
        )

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for i in range(p.block // c):
        rows = slice(i * c, (i + 1) * c)
        g_all, beta_all = g_ref[0, rows, :], beta_ref[0, rows, :]
        rstd = jnp.zeros((c, p.h), jnp.float32)
        for head in range(p.h):
            wide = slice(head * p.dk, (head + 1) * p.dk)
            deep = slice(head * p.dv, (head + 1) * p.dv)
            beta = _head_column(beta_all, head)
            ch, _, _ = _chunk_scalar(
                read(0, rows, wide), read(1, rows, wide),
                _head_column(g_all, head), beta, sub=p.sub, dt=dt,
            )
            w = _nn(ch.inv, beta * (ch.k * ch.e), dt)
            u_hat = _nn(ch.inv, beta * read(2, rows, deep), dt)
            s0 = state[head]  # [dv, dk]
            states_ref[0, i, head] = s0.astype(states_ref.dtype)
            u = u_hat - _nt(w, s0, dt)
            o = _nt(ch.q * ch.e, s0, dt) + _nn(ch.p_qk, u, dt)
            if p.out_norm is not None:  # the float32 tile, before rounding
                r = lax.rsqrt(
                    jnp.mean(o * o, axis=-1, keepdims=True) + p.out_norm
                )
                rstd = _set_column(rstd, head, r)
                o = o * r
            o_ref[0, rows, deep] = o.astype(o_ref.dtype)
            state[head] = s0 * ch.e_last + _tn(u, ch.k * ch.e_end, dt)
        if p.out_norm is not None:
            rstd_ref[0, rows, :] = rstd


def _whole_width(p: _Plan) -> _Plan:
    """The plan as the scalar-gate kernels' block specs and scratches see
    it: ONE "head" as wide as the operand, under a grid ``(batch, 1,
    blocks)``, so that :func:`_specs`, :func:`_door_specs` and
    :func:`_tiles` serve both forms."""
    return p._replace(dk=p.h * p.dk, dv=p.h * p.dv)


def _gdn_states_spec(p: _Plan, block_of):
    return pl.BlockSpec(
        (1, p.block // p.chunk, p.h, p.dv, p.dk),
        lambda bi, hi, i: (bi, block_of(i), 0, 0, 0), memory_space=_VMEM,
    )


@functools.partial(jax.jit, static_argnames=("p",), inline=True)
def _gdn_fwd_call(q, k, v, g, beta, conv=None, *, p: _Plan):
    with jax.named_scope(_GLUE_SCOPE):
        q, k, v, g, beta = (_pad(x, p) for x in (q, k, v, g, beta))
    w = _whole_width(p)
    wide, per_head, _ = _specs(w, lambda i: i)
    in_specs = [wide(w.dk), wide(w.dk), wide(w.dv), per_head, per_head]
    operands = [q, k, v, g, beta]
    scratch = [_VMEM((p.h, p.dv, p.dk), jnp.float32)]
    if p.taps:
        in_specs += _door_specs(w, lambda i: i)
        operands += [(q, k, v), tuple(conv)]
        scratch += [_tiles(w, _EDGE + p.block), _tiles(w, p.block)]
    out_specs = [wide(w.dv), _gdn_states_spec(p, lambda i: i)]
    out_shape = [
        jax.ShapeDtypeStruct((p.b, p.s_pad, w.dv), p.dtype),
        jax.ShapeDtypeStruct((p.b, p.n_chunks, p.h, p.dv, p.dk), p.dtype),
    ]
    if p.out_norm is not None:
        out_specs.append(per_head)
        out_shape.append(
            jax.ShapeDtypeStruct((p.b, p.s_pad, p.h), jnp.float32)
        )
    out, states, *rstd = pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, p=p),
        grid=(p.b, 1, p.s_pad // p.block),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_params(),
        interpret=p.interpret,
        name="hvd_gdn_fwd",
    )(*operands)
    with jax.named_scope(_GLUE_SCOPE):
        if rstd:
            return out[:, :p.s], states, rstd[0][:, :p.s]
        return out[:, :p.s], states


def _gdn_pair_backward(ch: _Chunk, decay, decay_t, dp_kk, dp_qk, dp_kk_t,
                       dp_qk_t, *, dt):
    """The scalar form's :func:`_pair_backward`: the decay matrix
    multiplies the gradients of ``P`` whole, then three matmuls."""
    c = ch.k.shape[0]
    rows_out = _nn(
        jnp.concatenate([dp_kk * decay, dp_qk * decay], axis=0), ch.k, dt
    )  # [2C, dk]
    dk_col = _nn(
        jnp.concatenate([dp_kk_t * decay_t, dp_qk_t * decay_t], axis=1),
        jnp.concatenate([ch.k, ch.q], axis=0), dt,
    )
    return rows_out[c:], rows_out[:c], dk_col


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                    *rest, p: _Plan):
    c, dt = p.chunk, p.dtype
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    own = (q_ref, k_ref, v_ref)
    rest = list(rest)
    if p.out_norm is not None:  # the forward's normalised output and 1 / rms
        out_ref, rstd_ref = rest.pop(0), rest.pop(0)
    if p.taps:
        (halos, taps, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dtaps,
         dstate, stages, convolved, slopes, ahead) = rest
        at_start = pl.program_id(2) == p.s_pad // p.block - 1
        for x in range(3):
            _convolve(own[x], halos[x], taps[x], stages[x], convolved[x],
                      slopes[x], at_start)
        read = lambda x, rows, lanes: convolved[x][rows, lanes]  # noqa: E731

        def write(x, rows, lanes, grad):  # before the SiLU, float32, staged
            ahead[x][rows, lanes] = grad * slopes[x][rows, lanes]

        @pl.when(pl.program_id(2) == 0)
        def _():
            for x in range(3):
                ahead[x][p.block:, :] = jnp.zeros_like(ahead[x][p.block:, :])
                dtaps[x][...] = jnp.zeros_like(dtaps[x])
    else:
        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate = rest
        read = lambda x, rows, lanes: f32(own[x][0, rows, lanes])  # noqa: E731

        def write(x, rows, lanes, grad):
            ref = (dq_ref, dk_ref, dv_ref)[x]
            ref[0, rows, lanes] = grad.astype(ref.dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    row, col, _, _ = _masks(c, 1)
    for i in reversed(range(p.block // c)):
        rows = slice(i * c, (i + 1) * c)
        g_all, beta_all = g_ref[0, rows, :], beta_ref[0, rows, :]
        dg_tile = jnp.zeros((c, p.h), jnp.float32)
        dbeta_tile = jnp.zeros((c, p.h), jnp.float32)
        for head in range(p.h):
            wide = slice(head * p.dk, (head + 1) * p.dk)
            deep = slice(head * p.dv, (head + 1) * p.dv)
            q_raw, k_raw = read(0, rows, wide), read(1, rows, wide)
            beta = _head_column(beta_all, head)
            ch, decay, decay_t = _chunk_scalar(
                q_raw, k_raw, _head_column(g_all, head), beta, sub=p.sub,
                dt=dt,
            )
            s0 = f32(states_ref[0, i, head])  # [dv, dk]
            k_e, q_e, k_end = ch.k * ch.e, ch.q * ch.e, ch.k * ch.e_end
            z = read(2, rows, deep) - _nt(k_e, s0, dt)
            u = _nn(ch.inv, beta * z, dt)
            ds = dstate[head]  # [dv, dk], of the state this chunk leaves
            do = f32(do_ref[0, rows, deep])
            if p.out_norm is not None:  # of o, given that of o rstd
                o_hat = f32(out_ref[0, rows, deep])
                do = _head_column(rstd_ref[0, rows, :], head) * (
                    do - o_hat * jnp.mean(do * o_hat, axis=-1, keepdims=True)
                )
            du = _tn(ch.p_qk, do, dt) + _nt(k_end, ds, dt)
            dr = _tn(ch.inv, du, dt)
            dz = beta * dr
            by_row = _nt(jnp.concatenate([dz, do, dr], axis=0), u, dt)
            by_col = _nt(u, jnp.concatenate([dz, do], axis=0), dt)
            below, above = col < row, col > row
            dbeta_tile = _set_column(dbeta_tile, head, (
                jnp.sum(dr * z, axis=-1, keepdims=True)
                - jnp.sum(jnp.where(below, by_row[2 * c:], 0.0) * ch.p_kk,
                          axis=-1, keepdims=True)
            ))
            dq_pair, dk_row, dk_col = _gdn_pair_backward(
                ch, decay, decay_t,
                jnp.where(below, -by_row[:c], 0.0),
                jnp.where(col <= row, by_row[c:2 * c], 0.0),
                jnp.where(above, -by_col[:, :c], 0.0),
                jnp.where(col >= row, by_col[:, c:], 0.0), dt=dt,
            )
            d_kg = -_nn(dz, s0, dt)  # of K e
            d_qg = _nn(do, s0, dt)  # of Q e
            d_kend = _nn(u, ds, dt)  # of K e_end
            dq = d_qg * ch.e + dq_pair
            dk = d_kg * ch.e + d_kend * ch.e_end + dk_row + dk_col
            to_end = jnp.sum(d_kend * k_end, axis=-1, keepdims=True)
            d_big_g = jnp.sum(
                d_qg * q_e + d_kg * k_e + ch.q * dq_pair
                + ch.k * (dk_row - dk_col), axis=-1, keepdims=True,
            ) - to_end
            # G_C, the last row's: every row's decay to the chunk's end
            # and the entry state's own
            to_last = jnp.sum(to_end, axis=0, keepdims=True) + ch.e_last * (
                jnp.sum(jnp.sum(s0 * ds, axis=-1, keepdims=True), axis=0,
                        keepdims=True)
            )
            d_big_g = d_big_g + jnp.where(
                lax.broadcasted_iota(jnp.int32, d_big_g.shape, 0) == c - 1,
                to_last, 0.0,
            )
            dg_tile = _set_column(dg_tile, head, jnp.sum(
                jnp.where(col >= row, _as_row(d_big_g), 0.0), -1,
                keepdims=True,
            ))
            write(0, rows, wide, _l2_bwd(q_raw, ch.rq, p.dk ** -0.5, dq))
            write(1, rows, wide, _l2_bwd(k_raw, ch.rk, 1.0, dk))
            write(2, rows, deep, dz)
            dstate[head] = ds * ch.e_last + _tn(
                jnp.concatenate([do, -dz], axis=0),
                jnp.concatenate([q_e, k_e], axis=0), dt,
            )
        dg_ref[0, rows, :] = dg_tile
        dbeta_ref[0, rows, :] = dbeta_tile

    if p.taps:  # over the block's whole width
        _door_backward(p, taps, stages, ahead, dtaps,
                       (dq_ref, dk_ref, dv_ref))


@functools.partial(jax.jit, static_argnames=("p",), inline=True)
def _gdn_bwd_call(q, k, v, g, beta, states, d_out, conv=None, normed=None, *,
                  p: _Plan):
    with jax.named_scope(_GLUE_SCOPE):
        q, k, v, g, beta, d_out = (
            _pad(x, p) for x in (q, k, v, g, beta, d_out)
        )
    last = p.s_pad // p.block - 1
    w = _whole_width(p)
    wide, per_head, _ = _specs(w, lambda i: last - i)
    like = lambda x, dtype: jax.ShapeDtypeStruct(x.shape, dtype)  # noqa: E731
    in_specs = [wide(w.dk), wide(w.dk), wide(w.dv), per_head, per_head,
                _gdn_states_spec(p, lambda i: last - i), wide(w.dv)]
    operands = [q, k, v, g, beta, states, d_out]
    out_specs = [wide(w.dk), wide(w.dk), wide(w.dv), per_head, per_head]
    out_shape = [
        like(q, p.dtype), like(k, p.dtype), like(v, p.dtype),
        like(g, jnp.float32), like(beta, jnp.float32),
    ]
    scratch = [_VMEM((p.h, p.dv, p.dk), jnp.float32)]
    if p.out_norm is not None:
        with jax.named_scope(_GLUE_SCOPE):
            operands += [_pad(x, p) for x in normed]
        in_specs += [wide(w.dv), per_head]
    if p.taps:
        in_specs += _door_specs(w, lambda i: last - i)
        operands += [(q, k, v), tuple(conv)]
        # the taps' gradients: one block a batch row that the sequence axis
        # revisits, rows >= n zero
        widths = (w.dk, w.dk, w.dv)
        out_specs.append(tuple(
            pl.BlockSpec((1, _EDGE, d), lambda bi, hi, i: (bi, 0, 0),
                         memory_space=_VMEM)
            for d in widths
        ))
        out_shape.append(tuple(
            jax.ShapeDtypeStruct((p.b, _EDGE, d), jnp.float32)
            for d in widths
        ))
        scratch += [_tiles(w, _EDGE + p.block), _tiles(w, p.block),
                    _tiles(w, p.block), _tiles(w, p.block + _EDGE)]
    dq, dk, dv, dg, dbeta, *d_taps = pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, p=p),
        grid=(p.b, 1, p.s_pad // p.block),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_params(),
        interpret=p.interpret,
        name="hvd_gdn_bwd",
    )(*operands)
    with jax.named_scope(_GLUE_SCOPE):
        grads = tuple(x[:, :p.s] for x in (dq, dk, dv, dg, dbeta))
    if not p.taps:
        return grads, None
    with jax.named_scope(_CONV_SCOPE):
        # [B, _EDGE, H d] partial sums -> [n, H d], summed over the batch
        return grads, KdaConv(*(
            x.sum(axis=0)[:p.taps] for x in d_taps[0]
        ))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda(q, k, v, g, beta, conv, p: _Plan):
    return _kda_fwd(q, k, v, g, beta, conv, p)[0]


def _kda_fwd(q, k, v, g, beta, conv, p: _Plan):
    _book(p, forward=True)
    call = _gdn_fwd_call if p.scalar else _fwd_call
    out, states, *rstd = call(q, k, v, g, beta, conv, p=p)
    # with the exit norm the normalised output is the backward's too: the
    # one copy kept, as the caller keeps it
    normed = (out, *rstd) if rstd else None
    return out, (q, k, v, g, beta, states, conv, normed)


def _kda_bwd(p: _Plan, residuals, d_out):
    _book(p, forward=False)
    *operands, conv, normed = residuals
    call = _gdn_bwd_call if p.scalar else _bwd_call
    grads, d_taps = call(*operands, d_out, conv, normed, p=p)
    return (*grads, d_taps)


_kda.defvjp(_kda_fwd, _kda_bwd)


# ---------------------------------------------------------------------------
# The recurrence itself
# ---------------------------------------------------------------------------


def kda_recurrence(q, k, v, g, beta, *, n_heads: int, group: int = 64):
    """The recurrence a position at a time in float32 (``lax.scan``),
    ``jax.checkpoint`` over groups of ``group`` positions so that the
    backward keeps one state a group. Same operands and result as
    :func:`kda_attention`."""
    b, s, _ = q.shape
    h = n_heads
    heads = lambda x: x.astype(jnp.float32).reshape(b, s, h, -1)  # noqa: E731
    q, k, v, g = heads(q), heads(k), heads(v), heads(g)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + NORM_EPS)
    q = q * q.shape[-1] ** -0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + NORM_EPS)
    beta = beta.astype(jnp.float32)[..., None]

    def step(state, x):  # state [b, h, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HI)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t * (v_t - read), precision=_HI
        )
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HI)

    group = min(group, s)
    pad = -s % group
    xs = [jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
          for x in (q, k, v, g, beta)]  # beta 0, g 0: the state stands
    xs = [jnp.moveaxis(x, 1, 0).reshape(-1, group, *x.shape[:1], *x.shape[2:])
          for x in xs]
    run = jax.checkpoint(lambda state, x: lax.scan(step, state, x))
    state = jnp.zeros((b, h, q.shape[-1], v.shape[-1]), jnp.float32)
    _, out = lax.scan(run, state, xs)  # [groups, group, b, h, dv]
    out = jnp.moveaxis(out.reshape(-1, b, h * v.shape[-1]), 0, 1)[:, :s]
    return out


def _scalar_gate(q, g, n_heads: int) -> bool:
    """Whether ``g`` is one log-decay a head (``[B, S, H]``: the scalar-gate
    form, Gated DeltaNet) and not one a key channel (``[B, S, H d_k]``)."""
    return g.shape[-1] == n_heads != q.shape[-1]


def kda_attention(q, k, v, g, beta, *, n_heads: int,
                  conv: Optional[KdaConv] = None,
                  out_norm: Optional[float] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  chunk: Optional[int] = None, sub: Optional[int] = None):
    """Kimi Delta Attention over ``q``, ``k``, ``g`` ``[B, S, H * d_k]``,
    ``v [B, S, H * d_v]`` and ``beta [B, S, H]``; returns ``[B, S, H *
    d_v]`` in ``v``'s dtype. ``q`` and ``k`` are normalised here (L2 over a
    head, eps 1e-6; ``q`` also times ``d_k^-1/2``); ``g`` is the log-decay
    of each key channel (<= 0) and ``beta`` the write strength, both
    float32. Differentiable in all five. ``g`` may instead be ``[B, S, H]``,
    one log-decay a head (Gated DeltaNet): the scalar-gate form of the
    kernels, whose heads may be of any width (``d_k`` 96, ``d_v`` 192).

    ``conv``: a :class:`KdaConv` of three tap arrays ``[n, H * d]`` float32
    (kernels only). ``q``, ``k``, ``v`` are then the PROJECTIONS' outputs:
    both kernels convolve a block's rows (depthwise, causal, zeros before
    row 0) and gate them with SiLU in VMEM, in float32, before anything
    else reads them, so the convolved operands never exist in HBM and the
    projections' outputs are the one copy the backward keeps.
    Differentiable in the taps too. ``None`` is the call without the
    argument, equation for equation.

    ``out_norm``: the ``eps`` (a Python float, static) of an RMS norm over
    each head's ``d_v`` channels at the kernels' exit (kernels only). The
    result is then ``o rsqrt(mean_d(o^2) + eps)``, no scale: the forward
    normalises a chunk's float32 tile in VMEM before its one rounding and
    leaves ``1 / rms`` a row and head beside it for the backward, which
    takes the normalised output's gradient, forms the plain one in VMEM
    and goes on as without. ``None`` is the call without the argument.

    ``use_kernel``: None takes the Pallas kernels where the world's
    devices are TPUs and the recurrence (:func:`kda_recurrence`) elsewhere;
    True runs the kernels anywhere (interpreted off the TPU). ``chunk`` /
    ``sub`` are the plan's statics (64 / 8), settable for tests."""
    if use_kernel is None:
        use_kernel = device_platform() == "tpu"
    if not use_kernel:
        if conv is not None:
            raise ValueError(
                "conv= is the kernels': on the recurrence path "
                "(use_kernel=False) hand in q, k, v convolved"
            )
        if out_norm is not None:
            raise ValueError(
                "out_norm= is the kernels': on the recurrence path "
                "(use_kernel=False) normalise the result"
            )
        return kda_recurrence(q, k, v, g, beta, n_heads=n_heads).astype(
            v.dtype
        )
    p = _plan(q, v, beta, n_heads=n_heads, chunk=chunk, sub=sub,
              interpret=interpret, conv=conv, out_norm=out_norm,
              scalar=_scalar_gate(q, g, n_heads))
    if conv is not None:
        conv = KdaConv(*(w.astype(jnp.float32) for w in conv))
    return _kda(
        q, k.astype(q.dtype), v.astype(q.dtype), g.astype(jnp.float32),
        beta.astype(jnp.float32), conv, p,
    )
