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
  ``[block_k, d] x [d, block_q]`` matmuls (the scores are held
  keys-by-queries, so what is kept per q row lies along the lanes), and
  the S×S score matrix is never materialized in HBM.
* :func:`flash_attention_with_lse` — same kernel, additionally returning
  the per-row log-sum-exp.  ``(out, lse)`` pairs are the composable form:
  ring attention merges one pair per ring hop with
  :func:`combine_blocks`, so the Pallas kernel is the per-step compute of
  the sequence-parallel path too.
* :func:`flash_attention_latent` — the same three kernels fed as latent
  attention holds its keys and values: one packed ``[k_nope | v]`` operand
  (``kv_b``'s output as it leaves the matmul) and the one rotary key every
  head of a position shares, so K is never built in HBM.

Both ``q, k, v`` entries take a ``window`` (a band under the diagonal: the
tiles and blocks outside it are skipped at both ends, in all three
kernels) and query groups (K and V with fewer heads than q: a program
reads ONE K/V head's block for the query heads that share it, and dK / dV
add up over them in the kernel).

Every entry takes ``q_rotary`` (:class:`QRotary`): q then enters as its
projection leaves it and a q block's rotated lanes are turned in VMEM, once
a block, where a lane rotation costs nothing; the forward hands the turned
block on as the backward's residual and dQ leaves turned back, so no
rotated q and no gradient of one is built by XLA ("Rotary at the door").
The ``q, k, v`` entries take ``q_norm`` (:class:`QNorm`) the same way: the
head-wise RMS norm of q runs on the block in VMEM ahead of the turn, and dQ
leaves as the gradient of the projection's output ("Norm at the door").

Causality across ring steps needs *global* positions, so the kernel takes
``q_offset``/``kv_offset`` (traced scalars, prefetched to SMEM): block r
of an ``sp``-sharded sequence holds global rows ``r*S .. (r+1)*S-1``.
Causal calls do score work only under the diagonal, at the granularity of
a compute tile finer than the copied block ("Causal tile geometry" below).

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
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..context import device_platform
from ..obs import registry as _registry

_SMEM = pltpu.SMEM
_VMEM = pltpu.VMEM

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "flash_attention_latent",
    "QRotary",
    "QNorm",
    "combine_blocks",
    "quantize_blockwise_pallas",
    "dequantize_blockwise_pallas",
    "fused_adamw_update_pallas",
    "int8_matmul_pallas",
    "fp8_matmul_pallas",
]

_NEG_INF = float(np.finfo(np.float32).min)
_LANES = 128
# ``jax.named_scope`` of the flash entries' own XLA operations around the
# three kernels (padding, the row statistics' layout, the slices back): the
# models' part ``attn_layout`` (docs/api.md).  The ``pallas_call``s themselves
# stay outside it, under their ``name=`` only.
_GLUE_SCOPE = "attn_layout"


# ``_head_group``'s budget for query heads that share a K/V head, and what
# such a call asks the compiler for (the default scoped limit is 16 MB and
# the dK/dV kernel's q, g, K, V blocks, two outputs and two accumulators
# pass it at 7 heads of 128 and blocks of 512 x 1024)
_GROUPED_VMEM = 10 << 20
_GROUPED_VMEM_LIMIT = 48 << 20


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _use_interpret() -> bool:
    return device_platform() != "tpu"


def _head_group(h: int, block_q: int, block_k: int, d: int,
                packed: bool, dv: Optional[int] = None, rope: int = 0,
                kv_ratio: int = 1) -> int:
    """Heads per program.  At short sequences a single head's two
    ``d``-thin matmuls underfill the MXU pipeline and per-program overhead
    (scalar DMAs, grid bookkeeping) dominates, so each program handles a
    group of heads (static unroll).  VMEM budget (~16 MB/core): the fp32
    accumulator and double-buffered q/k/v/o blocks scale with the group,
    and the compiler stacks per-head fp32 score transients on top, so cap
    the estimated block working set at ~4 MB (g=12 at S=512, D=64
    measured 18.4 MB of scoped vmem — over the 16 MB limit) and divide
    ``h`` evenly.  The blocks are the copied (DMA) blocks: a causal call
    walks them in smaller compute tiles (:func:`_compute_tile`), which
    shrinks the score transients but not what this budget counts.

    In the packed layout a group is the lane axis of the copied block, and
    Mosaic takes a block's minor dimension only as a multiple of the 128
    lanes or as the whole array's: 3 heads of 64 (192 lanes) are refused,
    2 and 6 of 6 are not.  Where no legal group fits the budget the
    smallest legal one is taken, and the compiler has the last word.

    ``d`` is the width of a q / k head, ``dv`` that of a v / out head
    (``None``: the same): the accumulator and the out block are ``dv``
    wide, and a packed group has to be lane-legal at both widths; with
    ``rope`` (:func:`flash_attention_latent`) also at ``d - rope + dv``,
    the width of a head of the packed ``kv``.

    ``kv_ratio`` > 1 (query groups: that many query heads share one K/V
    head): a program's K/V block is ONE K/V head's, so its query heads lie
    within that head's ``kv_ratio`` and the group divides it; the K/V
    blocks do not scale with the group.  The budget is then the whole of
    what the kernels hold, 10 MB of the 16 (``_GROUPED_VMEM``): at 7
    heads of 128 the 4 MB rule, written for groups that multiply K/V too,
    would leave one head a program, and one head a program fetches every
    K/V block seven times and pays the grid step seven times."""
    dv = d if dv is None else dv
    if kv_ratio > 1:
        for g in range(kv_ratio, 0, -1):
            if kv_ratio % g or (packed and g != h and any(
                (g * w) % _LANES for w in (d, dv)
            )):
                continue
            acc = g * block_q * dv * 4
            blocks = 2 * (g * block_q + block_k) * (d + dv) * 2
            if acc + blocks <= _GROUPED_VMEM or g == 1:
                return g
        return 1
    widths = (d, dv) + ((d - rope + dv,) if rope else ())

    def legal(g):
        return not packed or g == h or all(
            (g * w) % _LANES == 0 for w in widths
        )

    groups = [g for g in (12, 8, 6, 4, 3, 2, 1) if h % g == 0 and legal(g)]
    for g in groups:
        acc = g * block_q * dv * 4
        blocks = 2 * g * ((block_q + block_k) * d
                          + (block_k + block_q) * dv) * 2
        if acc + blocks <= 4 << 20:
            return g
    return groups[-1] if groups else h


# ---------------------------------------------------------------------------
# Causal tile geometry.  A causal call walks each copied [block_q, block_k]
# score block in compute tiles of [tq, tk] and sorts them into three classes
# by position alone:
#   above the diagonal (every entry masked)       -> not visited (and a
#                                                    block of only such
#                                                    tiles is not fetched);
#   below it (every entry valid, no padding)      -> the unmasked path, the
#                                                    one non-causal calls run;
#   straddling it (or holding padded columns, or
#   padded q rows in the backward)                -> the masked path.
# A ``window`` (a band: an entry is valid only where ``row - col < window``)
# adds the same two classes at the other end: tiles wholly left of the lower
# edge are not visited (and a block of only such tiles is neither fetched
# nor given a grid step), tiles that straddle it run the masked path.
# For one q tile the K/V tiles of a block come in that order — (left of the
# band, straddling its edge,) interior, straddling, skipped — so three
# counts describe it (``_tile_spans``).  The q
# tile then takes its visited tiles as one slab (``_for_causal_tiles``):
# unmasked if the whole block is interior, else masked.  The offsets stay
# traced scalars (ring attention passes ``rank * s``), so the class is a
# branch on scalars inside the kernel.
#
# The copied K/V block is the kernel's to widen (``_resident_kv``): what a
# q row pays per visit of a K/V block it pays once if the whole K/V is
# resident, and a copied block wider than the compute tile costs no score
# work because the classes are finer than it.
# ---------------------------------------------------------------------------


def _compute_tile(block: int, interpret: bool) -> int:
    """Edge of the compute tile for a copied block of ``block`` rows (or
    columns) of a causal call: half the block, halved again while it is
    over 256, as far as the layout allows — a multiple of the 128 lanes
    of a vreg when compiled (the tile's rows are the lane axis of the
    keys-by-queries scores, of the row statistics and of the ``[d, rows]``
    accumulators; its columns are the contraction the MXU takes 128 deep),
    of the 8 sublanes of one in the interpreter, where small test shapes
    must still cross every class.  Measured on the v5e at s 1024, d 64
    (PERF.md, PR 25): 128 loses what 256 gains, because the per-row
    softmax bookkeeping is paid per q tile and does not shrink with it."""
    floor = 8 if interpret else _LANES
    tile = block
    while tile % (2 * floor) == 0 and (tile == block or tile > 256):
        tile //= 2
    return tile


_SLAB_TILES = 4


def _resident_kv(block_k: int, tk: int, skv_pad: int) -> int:
    """K/V rows a causal call copies per grid step, given the caller's
    ``block_k``, its compute tile and the padded K/V length: as many whole
    ``block_k`` blocks as ``_SLAB_TILES`` tiles hold and as divide the K/V
    evenly (no more padding than the caller's block gives).  At s 1024,
    d 64 that is all of K/V, 1024 rows in four tiles of 256 (measured on
    the v5e, PERF.md PR 25: 2,602 us a layer against 3,050 at 512 rows).
    The bound is in tiles because each slab width is a traced body of its
    own and the widest slab's fp32 scores, ``[tq, 4 tk]``, are the
    kernels' largest transient; the bytes are ``_head_group``'s to fit."""
    blocks = skv_pad // block_k
    for m in range(min(blocks, _SLAB_TILES * tk // block_k), 0, -1):
        if blocks % m == 0:
            return m * block_k
    return block_k


def _clip(x, lo, hi):
    if isinstance(x, int):
        return min(max(x, lo), hi)
    return jnp.clip(x, lo, hi)


def _tile_spans(row0, col0, cols_real, q_padded, *, tq: int, tk: int,
                nkt: int, window: Optional[int] = None):
    """Classes of the ``nkt`` K/V compute tiles of one block against one
    q tile, as ``(n_left, n_interior, n_visited)``: tiles ``[0, n_left)``
    lie wholly left of the band's lower edge and hold no valid entry
    (``window`` only; else 0), tiles ``[n_left, n_visited)`` are visited,
    ``[n_visited, nkt)`` lie beyond the diagonal and hold no valid entry.
    Of the visited tiles ``n_interior`` hold only valid entries (they lie
    together: after those that straddle the lower edge, before those that
    straddle the diagonal or hold padding).

    ``row0``: global position of the q tile's first row; ``col0``: global
    position of the block's first column; ``cols_real``: how many of the
    block's columns lie before the K/V length (any integer); ``q_padded``:
    the q tile holds padded rows whose ``lse`` is ``-inf`` (backward only);
    ``window``: an entry is valid only where ``row - col < window``.
    Python ints give Python ints (the build-time counter and the tests);
    traced scalars give traced scalars (the kernels)."""
    span = nkt * tk
    # tile j is visited iff its first column col0 + j*tk <= row0 + tq - 1
    n_visited = _clip(row0 + tq - col0 + tk - 1, 0, span) // tk
    # ... and interior iff its last column col0 + (j+1)*tk - 1 <= row0
    below = _clip(row0 - col0 + 1, 0, span) // tk
    unpadded = _clip(cols_real, 0, span) // tk
    static = isinstance(below, int) and isinstance(unpadded, int)
    n_left = 0
    if window is not None:
        # tile j lies left of the band iff its last column is below the
        # FIRST row's lowest, col0 + (j+1)*tk - 1 < row0 - window + 1 ...
        n_left = _clip(row0 - window + 1 - col0, 0, span) // tk
        # ... and is cut by the lower edge iff its first column is below
        # the LAST row's lowest, col0 + j*tk < row0 + tq - window
        on_edge = _clip(row0 + tq - window - col0 + tk - 1, 0, span) // tk
        if static:
            n_left = min(n_left, n_visited)
            below, unpadded = (max(x - on_edge, 0) for x in (below, unpadded))
        else:
            n_left = jnp.minimum(n_left, n_visited)
            below, unpadded = (
                jnp.maximum(x - on_edge, 0) for x in (below, unpadded)
            )
    if static:
        n_interior = 0 if q_padded else min(below, unpadded)
    else:
        n_interior = jnp.where(q_padded, 0, jnp.minimum(below, unpadded))
    return n_left, n_interior, n_visited


def _for_causal_tiles(tile, row0, col0, cols_real, rows_real, *,
                      block_q: int, block_k: int, tq: int, tk: int,
                      window: Optional[int] = None):
    """Inside a kernel: call ``tile(r, start, width, masked)`` once for
    every q tile of one copied block that sees a valid entry of it: ``r``
    the tile's first row within the block (traced), ``start`` the first
    column it visits (0, or traced under a ``window``: the tiles left of
    the band are passed over) and ``width`` (static) how many columns from
    there, a whole number of K/V tiles.  A q tile takes its visited
    columns as ONE slab: the
    running max / sum / accumulator are read, rescaled and written once
    per slab, and that per-row work, not the entries, is what narrow
    tiles multiply.  The slab is unmasked when every tile of the block is
    interior, else masked as a whole.  ``row0`` / ``col0``: global
    positions of the block's first row / column; ``rows_real``: how many
    of the block's rows are real (``None``: padded rows need no guard,
    as in the forward).  ``tile`` is traced once per width and path.
    Masking only the straddling tiles (the interior ones as an unmasked
    slab, then each straddling tile) was measured in the two backward
    kernels, which keep no per-row state, and lost 25% / 20% to the
    second update per q tile (PERF.md, PR 25)."""
    nkt = block_k // tk

    def q_tile(i, carry):
        r = i * tq
        q_padded = False if rows_real is None else r + tq > rows_real
        n_left, n_interior, n_visited = _tile_spans(
            row0 + r, col0, cols_real, q_padded, tq=tq, tk=tk, nkt=nkt,
            window=window,
        )
        n_slab = n_visited if window is None else n_visited - n_left
        pl.when(n_interior == nkt)(lambda: tile(r, 0, block_k, False))
        for w in range(1, nkt + 1):
            pl.when(jnp.logical_and(n_slab == w, n_interior < nkt))(
                functools.partial(tile, r, n_left * tk, w * tk, True)
            )
        return carry

    lax.fori_loop(0, block_q // tq, q_tile, 0)


def _count_tiles(q_offset: int, kv_offset: int, *, sq: int, skv: int,
                 sq_pad: int, skv_pad: int, block_q: int, block_k: int,
                 tq: int, tk: int, guard_q_pad: bool,
                 window: Optional[int] = None):
    """``(visited, masked, skipped)`` compute tiles of one batch element
    and head, by the kernels' own rule (``_tile_spans``; a q tile's
    visited tiles of one block run masked unless all are interior)."""
    visited = masked = 0
    for q0 in range(0, sq_pad, tq):
        for k0 in range(0, skv_pad, block_k):
            n_left, n_interior, n_visited = _tile_spans(
                q_offset + q0, kv_offset + k0, skv - k0,
                guard_q_pad and q0 + tq > sq,
                tq=tq, tk=tk, nkt=block_k // tk, window=window,
            )
            visited += n_visited - n_left
            if n_interior < block_k // tk:  # the slab is masked as a whole
                masked += n_visited - n_left
    return visited, masked, (sq_pad // tq) * (skv_pad // tk) - visited


def _book_call_kinds(p: "_Plan", kernels: int,
                     backward: bool = False) -> None:
    """Build-time counters of what kind of call ``kernels`` kernels were
    built for: ``flash.calls.latent_kv``, ``.windowed``, ``.grouped_kv``;
    ``flash.calls.rotary_q`` / ``flash.calls.norm_q``, one a kernel that
    turns / norms (the forward and dQ: dK/dV reads the forward's q); and
    ``flash.calls.delta_q``, one a ``backward``: its dQ kernel makes the
    row statistic ("Δ at the door")."""
    reg = _registry.always()
    if backward:
        reg.counter("flash.calls.delta_q").inc()
    for name, on in (("latent_kv", p.rope), ("windowed", p.window),
                     ("grouped_kv", p.kv_ratio > 1)):
        if on:
            reg.counter(f"flash.calls.{name}").inc(kernels)
    for name, on in (("rotary_q", p.turn), ("norm_q", p.norm)):
        if on is not None:
            reg.counter(f"flash.calls.{name}").inc()


def _book_tiles(static_offsets, **geometry) -> None:
    """Build-time counters of one causal ``pallas_call`` (always on, like
    ``build.*``): what the tile geometry makes of it."""
    reg = _registry.always()
    if static_offsets is None:
        reg.counter("flash.calls.dynamic_offsets").inc()
        return
    counts = _count_tiles(*static_offsets, **geometry)
    for name, n in zip(("visited", "masked", "skipped"), counts):
        reg.counter(f"flash.tiles.{name}").inc(n)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _head(ref, g, d, packed, rows=slice(None)):
    """Per-head block accessor, optionally of a row range only.
    ``packed=False``: heads on a leading block dim (``ref[0, g]`` —
    page-select slice).  ``packed=True``: heads packed in the minor
    (lane) axis of a ``[1, rows, G*d]`` block — a static lane slice at
    ``g*d`` (Mosaic supports 64-aligned lane slicing; probed on v5e),
    which lets q/k/v arrive in the projection's native ``[B, S, H*D]``
    layout with no relayout anywhere."""
    if packed:
        return ref[0, rows, g * d:(g + 1) * d]
    return ref[0, g, rows, :]


def _head_store(ref, g, d, packed, value):
    if packed:
        ref[0, :, g * d:(g + 1) * d] = value
    else:
        ref[0, g] = value


# Rotary at the door (``q_rotary=``, :class:`QRotary`).  A call given the
# tables takes q as its projection leaves it.  The forward turns a q block's
# rotated lanes when the block arrives (grid step 0 of its K/V axis: once a
# block and head, not once a tile visited), in float32, rounded once to q's
# dtype, into a further OUTPUT block that stays in VMEM through the K/V steps
# and is what the updates read; written back, it is the backward's residual
# in place of q, so dK/dV and dQ read the turned q and turn nothing.  dQ's
# finalize, where the ``[d, block_q]`` accumulator is turned to row-major
# anyway, turns the rotated lanes back by the transposed rotation in
# float32: what leaves is the gradient of the UNROTATED q, rounded once.
# The tables reach the kernels as one float32 ``[S, 2 r]`` operand, ``[cos |
# sin]`` spread over the ``r`` rotated lanes with the sign each lane takes
# (``_rotary_operand``), so a turn is ``x * cos + partner(x) * sin`` and the
# partner is a lane rotation: by half the width (halves), or by one lane
# either way and a select on the lane's parity (adjacent pairs).  Both turns
# work on the block's lanes as they lie, vreg tile by vreg tile
# (``_turn_lanes``): head by head, the 64 rotated lanes of a 192-lane head
# were cut out, widened, turned, set back between their neighbours and
# stored at a 64-lane offset, and the forward paid 0.68 us a head for it
# (kernels alone, PERF.md PR 41).
# ---------------------------------------------------------------------------


def _turn_tile(x, rot, inside, turn, back: bool):
    """One lane tile ``x``, ``[rows, T]``, in float32 with the lanes of
    ``inside`` (``[(first, last + 1)]``, each ``r`` wide) turned; ``rot``:
    the rows' ``[rows, 2 r]`` table block.  The other lanes meet a cosine of
    one and a sine of zero, so the whole tile is one multiply-add and what
    the rotations bring into them does not count."""
    _, r, halves = turn
    rows, width = x.shape
    x = x.astype(jnp.float32)
    # a lane rotation takes whole vregs: a narrower tile is set beside
    # itself, and the first ``width`` lanes of the ring are its own rotation
    ring = x if width % _LANES == 0 else jnp.concatenate([x, x], axis=1)
    lanes = ring.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, ring.shape, 1)
    if not halves:  # ranges start on even lanes (``_rotary_operand``)
        partner = jnp.where(
            lane % 2 == 0, pltpu.roll(ring, lanes - 1, 1),
            pltpu.roll(ring, 1, 1),
        )
    elif r == lanes:
        partner = pltpu.roll(ring, r // 2, 1)
    else:
        upper = functools.reduce(jnp.logical_or, [
            jnp.logical_and(lane >= a + r // 2, lane < b) for a, b in inside
        ])
        partner = jnp.where(
            upper, pltpu.roll(ring, r // 2, 1),
            pltpu.roll(ring, lanes - r // 2, 1),
        )
    if lanes != width:
        partner = partner[:, :width]

    def spread(table, fill):
        pieces, at = [], 0
        for a, b in inside:
            if a > at:
                pieces.append(jnp.full((rows, a - at), fill, jnp.float32))
            pieces.append(table)
            at = b
        if at < width:
            pieces.append(jnp.full((rows, width - at), fill, jnp.float32))
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(
            pieces, axis=1
        )

    sin = rot[:, r:]
    return x * spread(rot[:, :r], 1.0) + partner * spread(
        -sin if back else sin, 0.0
    )


def _lane_views(packed: bool, group: int):
    """Index prefixes of a q block's ``[rows, lanes]`` views, with the heads
    each holds: the packed block's one view of all ``group`` heads, or a
    view a head."""
    if packed:
        return [((0,), list(range(group)))]
    return [((0, g), [g]) for g in range(group)]


def _turn_lanes(load, store, width: int, heads: int, d: int, rot, turn,
                back: bool = False):
    """``store(a, b, turned)`` for every lane tile ``[a, b)`` of a
    ``[rows, width]`` array that ``load(a, b)`` reads, ``heads`` heads of
    ``d`` lanes side by side, with each head's rotated lanes turned
    (``back``: by the transposed rotation) in float32; a tile without any is
    handed on as loaded.  The tiles are the 128 lanes of a vreg where they
    divide the array and cut no head's rotated lanes (the cells: 2 heads of
    128 + 64 are three tiles, two of which hold 64 rotated lanes in one
    half; a head of 128 is one), so nothing is shifted along the lanes but
    what the rotation itself shifts; else the whole array is one tile."""
    lo, r, _ = turn
    ranges = [(g * d + lo, g * d + lo + r) for g in range(heads)]
    tile = _LANES if width % _LANES == 0 and all(
        a // _LANES == (b - 1) // _LANES for a, b in ranges
    ) else width
    for t in range(0, width, tile):
        inside = [(a - t, b - t) for a, b in ranges if t <= a < t + tile]
        x = load(t, t + tile)
        store(t, t + tile,
              _turn_tile(x, rot, inside, turn, back) if inside else x)


# Norm at the door (``q_norm=``, :class:`QNorm`), beside the rotary and at
# the same two places.  q's head-wise RMS norm, ``z = x rsqrt(mean_d(x x) +
# eps) scale`` over each head's ``d`` lanes, reshapes ``[S, H d]`` to ``[S,
# H, d]`` where XLA forms it, which changes the tiling: XLA sets float32
# copies of q's shape around the statistic, in both directions.  A call given
# the scale takes q as its PROJECTION leaves it.  The forward norms a head of
# the block in float32 where the turn runs (step 0 of the K/V axis), hands
# the float32 lanes to the turn, and what is rounded, once, into the second
# output is the q the scores see: the backward's residual and what
# ``return_q`` hands the caller.  dQ's finalize, behind the turn back, holds
# ``gz``, the float32 gradient of a head's normed lanes; with the raw q
# block and the scale as two more operands it recomputes ``r = rsqrt(..)``
# and ``y = x r`` and writes ``dx = r (gz scale - y mean_d(gz scale y))``,
# the gradient of the projection's output, and ``sum_rows(gz y)`` over the
# program's heads as one float32 ``[1, d]`` row a program, which XLA adds up
# to the scale's gradient.  dK/dV reads the residual and is the call it was.
# ---------------------------------------------------------------------------


def _inv_rms(x, eps: float):
    """``rsqrt(mean(x x) + eps)`` over the lanes of a float32 ``[rows, d]``
    head, ``[rows, 1]``."""
    return lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)


def _lanes_of(pieces, a: int, b: int):
    """Lanes ``[a, b)`` out of ``pieces``, ``{(first, last + 1): [rows,
    last + 1 - first]}`` side by side; a piece that is the range is handed
    on as it is."""
    cut = [
        x if (lo, hi) == (max(a, lo), min(b, hi))
        else x[:, max(a, lo) - lo:min(b, hi) - lo]
        for (lo, hi), x in sorted(pieces.items()) if lo < b and a < hi
    ]
    return cut[0] if len(cut) == 1 else jnp.concatenate(cut, axis=1)


def _normed_lanes(load, heads: int, d: int, scale, eps: float):
    """``load`` (lanes ``[a, b)`` of ``heads`` heads of ``d`` side by side)
    with every head normed: float32, each head formed once, when a range
    first touches it; ``scale``: float32 ``[1, d]``."""
    normed = {}

    def lanes(a, b):
        for i in range(a // d, (b - 1) // d + 1):
            if (i * d, (i + 1) * d) not in normed:
                x = load(i * d, (i + 1) * d).astype(jnp.float32)
                normed[i * d, (i + 1) * d] = x * _inv_rms(x, eps) * scale
        return _lanes_of(normed, a, b)

    return lanes


# Where a kernel finds head ``g``'s keys and values in its second and third
# block operand.  ``rope == 0``: they are K, ``[.., d]`` a head, and V,
# ``[.., dv]`` a head.  ``rope == r > 0`` (:func:`flash_attention_latent`):
# the second is the packed ``kv``, ``[k_nope | v]`` a head (``d - r`` and
# ``dv`` wide, both static lane slices of the one copied block), and the
# third the ``[rows, r]`` rotary key that every head shares.  A head's
# ``[rows, d]`` key tile is then put together in VMEM, tile by tile, and no
# key of that width exists in HBM.  Measured on the v5e at the expert
# cell's shape (PERF.md, PR 37): the scores as two dots into one sum,
# ``k_nope·q_nopeᵀ + k_rope·q_ropeᵀ``, cost the forward and dK/dV the same
# and dQ, whose ``Kᵀ·dsᵀ`` then falls into two products as well, 5% more.


def _k_head(k_ref, v_ref, g, rows, *, packed, d, dv, rope,
            kv_shared=False):
    """Head ``g``'s ``[rows, d]`` key tile.  ``kv_shared`` (query groups):
    the block is the ONE K/V head every query head of the program reads."""
    g = 0 if kv_shared else g
    if rope:
        lo = g * (d - rope + dv)
        return jnp.concatenate(
            [k_ref[0, rows, lo:lo + d - rope], v_ref[0, rows, :]], axis=1
        )
    return _head(k_ref, g, d, packed, rows)


def _v_head(k_ref, v_ref, g, rows, *, packed, d, dv, rope,
            kv_shared=False):
    """Head ``g``'s ``[rows, dv]`` value tile."""
    g = 0 if kv_shared else g
    if rope:
        hi = (g + 1) * (d - rope + dv)
        return k_ref[0, rows, hi - dv:hi]
    return _head(v_ref, g, dv, packed, rows)


def _block_dims(q_ref, k_ref, packed: bool, d: int):
    """``(group, block_q, block_k)`` of a kernel's q and K/V blocks."""
    if packed:
        return q_ref.shape[2] // d, q_ref.shape[1], k_ref.shape[1]
    return q_ref.shape[1], q_ref.shape[2], k_ref.shape[2]


def _valid_mask(geom, row0, col0, tq: int, tk: int, causal: bool,
                window: Optional[int] = None):
    """[tk, tq] validity of the keys-by-queries scores whose first q row
    sits at global position ``row0`` and whose first K/V column is
    ``col0``: columns on sublanes, q positions on lanes."""
    col = col0 + lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
    valid = col < geom[2]  # mask K/V padding
    if causal:
        q_pos = row0 + lax.broadcasted_iota(jnp.int32, (1, tq), 1)
        valid = jnp.logical_and(valid, q_pos >= geom[1] + col)
        if window is not None:
            valid = jnp.logical_and(valid, q_pos < geom[1] + col + window)
    return valid


def _in_band(band, block, blocks: int):
    """Runs a function at once, or under a window only where the block
    the grid step stands for (number ``block`` of ``blocks``) lies inside
    the padded sequence: a windowed grid axis starts at the band's first
    block, so its last steps can lie beyond the end, where the index map
    repeats a block that must not be counted twice."""
    if band is None:
        return lambda f: f()
    return pl.when(block < blocks)


def _drive_tiles(update, geom, qi, kj, *, q_len: Optional[int],
                 block_q: int, block_k: int, causal: bool, masked: bool,
                 tiles: Tuple[int, int], window: Optional[int] = None,
                 keep_ref=None):
    """Drive a kernel's ``update(rq, rk, valid)`` over one (q block, K/V
    block) pair: ``rq`` / ``rk`` select the q / K/V rows, ``valid`` is
    their ``[K/V rows, q rows]`` validity mask or ``None`` on the unmasked
    path.  Causal: slab by slab (``_for_causal_tiles``); else the whole
    block at once, masked only if ``masked``.  ``geom``: the scalars ``(q_offset, kv_offset,
    kv_len)``; ``q_len``: the real q length where padded q rows need the
    masked path (the backward), else ``None``.  ``keep_ref`` (a causal call
    handed a mask, ``keep=``): the pair's ``[1, block_k, block_q]`` int8
    block of it, ANDed into what the geometry gives; an interior slab's
    validity is then the mask's alone."""
    row0 = geom[0] + qi * block_q  # global position of the block's row 0
    if causal:
        tq, tk = tiles

        def tile(r, start, width, tile_masked):
            # a slab starts at column 0 unless a window's lower edge cuts
            # the block: then at a K/V tile, a traced one
            cut = not isinstance(start, int)
            rk = pl.ds(pl.multiple_of(start, tk), width) if cut else slice(
                0, width
            )
            valid = _valid_mask(
                geom, row0 + r, kj * block_k + start if cut else kj * block_k,
                tq, width, True, window,
            ) if tile_masked else None
            rq = pl.ds(pl.multiple_of(r, tq), tq)
            if keep_ref is not None:
                kept = keep_ref[0, rk, rq].astype(jnp.int32) != 0
                valid = kept if valid is None else jnp.logical_and(valid, kept)
            update(rq, rk, valid)

        _for_causal_tiles(
            tile, row0, geom[1] + kj * block_k,
            geom[2] - kj * block_k,
            None if q_len is None else q_len - qi * block_q,
            block_q=block_q, block_k=block_k, tq=tq, tk=tk, window=window,
        )
    else:
        update(
            slice(None), slice(None),
            _valid_mask(geom, row0, kj * block_k, block_q, block_k, False)
            if masked else None,
        )


def _fwd_kernel(
    qoff_ref,
    kvoff_ref,
    kvlen_ref,
    q_ref,
    k_ref,
    v_ref,
    *refs,
    sm_scale: float,
    causal: bool,
    masked: bool,
    tiles: Tuple[int, int],
    packed: bool = False,
    d: int = 0,
    dv: int = 0,
    rope: int = 0,
    kv_shared: bool = False,
    band: Optional["_Plan"] = None,
    turn: Optional[Tuple[int, int, bool]] = None,
    select: bool = False,
    norm: Optional[float] = None,
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

    The scores are held keys-by-queries, ``sᵀ = K·Qᵀ`` as ``[cols, rows]``,
    so everything kept per q row lies along the lanes: the running max
    and sum are ``[1, rows]`` vectors (a reduction over a score column is
    a reduction over sublanes, and they broadcast back over sublanes as
    they lie), ``lse`` leaves in the layout it is stored in, and the
    accumulator is ``accᵀ = Vᵀ·pᵀ``, ``[d, rows]``, all of whose lanes
    are live at ``d`` 64.  What is transposed is small: ``V``'s
    ``[cols, d]`` tile per update and the accumulator once per q block.
    Against ``[rows, 1]`` statistics beside ``[rows, cols]`` scores this
    took 13% off the kernel at s 1024, causal, and 56% at s 512 (PERF.md,
    PR 31).

    A causal call walks the block in ``tiles = (tq, tk)`` compute tiles
    (see "Causal tile geometry"): tiles above the diagonal are not
    visited, tiles below it run the unmasked update.  Any other call
    updates the whole block at once, masked only if K/V is padded.

    qoff_ref / kvoff_ref / kvlen_ref: SMEM int32 [1, 1]; q_ref:
    [1, G, block_q, d]; k_ref: [1, G, block_k, d]; v_ref: [1, G, block_k,
    dv]; o_ref: [1, G, block_q, dv]; lse_ref: [1, G, 8, block_q] (8 = min
    sublane tile; caller reads sublane 0); acc_ref: [G, dv, block_q];
    m_ref / l_ref: [G, 1, block_q].  ``d`` is the width of a q / k head,
    ``dv`` of a v / out head (latent attention: 192 and 128); nothing in
    the body is score-by-width, so one body serves both.  With ``rope``
    k_ref is the packed ``kv`` block, [1, block_k, G*(d - rope + dv)],
    and v_ref the shared key's, [1, block_k, rope] (``_k_head``).  With
    ``kv_shared`` (query groups) k_ref / v_ref hold ONE head, which every
    query head of the program reads.  ``band`` (the call's plan, under a
    window only): the K/V grid axis covers the blocks the band can reach
    and step 0 is the q block's first (``_first_kv_block``).  With
    ``turn`` ("Rotary at the door") the refs after v_ref are ``rot_ref,
    o_ref, lse_ref, qt_ref`` and the scratch: the rows' table block
    ``[block_q, 2 r]`` and a further output shaped like the q block, which
    step 0 fills with the turned q and every update reads in q_ref's place.
    With ``norm`` (an ``eps``: "Norm at the door") the scale, float32 ``[1,
    d]``, comes behind the table, and what step 0 writes to that further
    output is the normed q, turned if ``turn``.
    With ``select`` (``keep=``) the last input is the pair's block of the
    mask, ``[1, block_k, block_q]`` int8 (``_drive_tiles``).
    """
    refs = list(refs)
    rot_ref = refs.pop(0) if turn is not None else None
    scale_ref = refs.pop(0) if norm is not None else None
    keep_ref = refs.pop(0) if select else None
    if turn is None and norm is None:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        qt_ref = q_ref
    else:
        o_ref, lse_ref, qt_ref, acc_ref, m_ref, l_ref = refs
    geom = (qoff_ref[0, 0], kvoff_ref[0, 0], kvlen_ref[0, 0])
    group, block_q, block_k = _block_dims(q_ref, k_ref, packed, d)
    heads = dict(packed=packed, d=d, dv=dv, rope=rope, kv_shared=kv_shared)
    qi = pl.program_id(2)
    kj = step = pl.program_id(3)
    nk = pl.num_programs(3)
    if band is not None:
        kj = step + _first_kv_block(
            qi, (qoff_ref, kvoff_ref, kvlen_ref), band
        )

    @pl.when(step == 0)
    def _init():
        acc_ref[:, :, :] = jnp.zeros_like(acc_ref)
        m_ref[:, :, :] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:, :, :] = jnp.zeros_like(l_ref)
        if qt_ref is not q_ref:
            for at, heads in _lane_views(packed, group):
                def put(a, b, x, at=at):
                    qt_ref[(*at, slice(None), slice(a, b))] = x.astype(
                        qt_ref.dtype
                    )

                def lanes(a, b, at=at):
                    return q_ref[(*at, slice(None), slice(a, b))]

                if norm is not None:
                    lanes = _normed_lanes(
                        lanes, len(heads), d, scale_ref[...], norm
                    )
                if turn is None:
                    for a in range(0, len(heads) * d, d):
                        put(a, a + d, lanes(a, a + d))
                    continue
                _turn_lanes(
                    lanes, put, q_ref.shape[-1], len(heads), d, rot_ref[...],
                    turn,
                )

    def update(rq, rk, valid):
        """Online-softmax update of q rows ``rq`` with K/V rows ``rk``.
        ``valid=None`` is the unmasked path: non-causal unpadded calls
        and interior causal tiles skip the validity passes entirely — the
        kernel is VPU-bound at short S, so every elementwise pass over
        the scores counts."""
        for g in range(group):
            # Matmul inputs stay in their storage dtype (bf16 on TPU):
            # the MXU is native bf16xbf16->fp32; upcasting to fp32 first
            # costs ~4-6 MXU passes per dot (measured 15% kernel
            # efficiency before this).  Softmax statistics are fp32.
            s_t = jax.lax.dot_general(
                _k_head(k_ref, v_ref, g, rk, **heads),
                _head(qt_ref, g, d, packed, rq),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [cols, rows] fp32
            if valid is not None:
                s_t = jnp.where(valid, s_t, _NEG_INF)

            m = m_ref[g, :, rq]  # [1, rows]
            l = l_ref[g, :, rq]
            m_new = jnp.maximum(m, jnp.max(s_t, axis=0, keepdims=True))
            # m_new == NEG_INF only for rows with no valid column so far;
            # keep exponent args finite there (p is zeroed by the mask).
            m_safe = m_new if valid is None else jnp.maximum(
                m_new, _NEG_INF / 2
            )
            p_t = jnp.exp(s_t - m_safe)
            if valid is not None:
                p_t = jnp.where(valid, p_t, 0.0)
            corr = jnp.exp(m - m_safe)
            l_ref[g, :, rq] = l * corr + jnp.sum(p_t, axis=0, keepdims=True)
            m_ref[g, :, rq] = m_new
            # p in the V dtype for a native-MXU dot (fp32 accumulate
            # keeps the reduction exact; the p rounding is the standard
            # flash trade).  Vᵀ·pᵀ: the thin operand is the one turned.
            acc_ref[g, :, rq] = acc_ref[g, :, rq] * corr + jax.lax.dot_general(
                _v_head(k_ref, v_ref, g, rk, **heads),
                p_t.astype(v_ref.dtype),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [dv, rows]

    _in_band(band, kj, band and band.skv_pad // block_k)(lambda: _drive_tiles(
        update, geom, qi, kj, q_len=None, block_q=block_q, block_k=block_k,
        causal=causal, masked=masked, tiles=tiles,
        window=band and band.window, keep_ref=keep_ref,
    ))

    @pl.when(step == nk - 1)
    def _finalize():
        for g in range(group):
            l = l_ref[g, :, :]  # [1, block_q]
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
                o_ref, g, dv, packed,
                (acc_ref[g, :, :] / l_safe).T.astype(o_ref.dtype),
            )
            lse_ref[0, g] = jnp.broadcast_to(
                lse, (lse_ref.shape[2], block_q)
            )


class _Plan(NamedTuple):
    """Shapes and tiling of one flash call, shared by its three kernels."""

    packed: bool
    b: int
    h: int
    d: int
    sq: int
    skv: int
    block_q: int
    block_k: int
    sq_pad: int
    skv_pad: int
    group: int
    tiles: Tuple[int, int]  # compute tile; the whole block unless causal
    interpret: bool
    dv: int  # width of a v / out head; ``d`` is that of a q / k head
    # 0: k and v are operands of their own.  r > 0: the packed ``kv`` and
    # the shared ``[.., r]`` key stand in their place (``_k_head``)
    rope: int = 0
    # query heads that share one K/V head (1: each its own)
    kv_ratio: int = 1
    # 0: none.  W > 0: an entry is valid only where ``row - col < W``
    window: int = 0
    # None: q arrives rotated, if at all.  ``(first rotated lane of a head,
    # rotated lanes, halves)``: the kernels turn it ("Rotary at the door")
    turn: Optional[Tuple[int, int, bool]] = None
    # the call was handed a mask (``keep=``): one more int8 operand, a
    # ``[block_k, block_q]`` block a pair, in all three kernels
    select: bool = False
    # None: q arrives normed, if at all.  An ``eps``: the kernels norm each
    # head of it ("Norm at the door")
    norm: Optional[float] = None

    @property
    def door(self) -> bool:
        """The forward writes the q its scores see (normed, turned) as a
        further output, and the backward reads that in q's place."""
        return self.turn is not None or self.norm is not None

    @property
    def kv_group(self) -> int:
        """K/V heads in a program's K/V block."""
        return self.group if self.kv_ratio == 1 else 1

    @property
    def subs(self) -> int:
        """Programs (groups of query heads) that share one K/V head."""
        return self.kv_ratio // self.group if self.kv_ratio > 1 else 1

    @property
    def kv_steps(self) -> int:
        """Grid steps of the K/V axis a q block takes (forward, dQ): all
        K/V blocks, or under a window as many as ``window + block_q - 1``
        columns in a row can touch wherever they start."""
        blocks = self.skv_pad // self.block_k
        if not self.window:
            return blocks
        return min(
            blocks, (self.window + self.block_q - 3) // self.block_k + 2
        )

    @property
    def q_steps(self) -> int:
        """Grid steps of the q axis a K/V block takes (dK/dV)."""
        blocks = self.sq_pad // self.block_q
        if not self.window:
            return blocks
        return min(
            blocks, (self.window + self.block_k - 3) // self.block_q + 2
        )

    def pad_seq(self, x, s: int, s_pad: int):
        if s_pad != s:
            pads = [(0, 0)] * x.ndim
            pads[1 if self.packed else 2] = (0, s_pad - s)
            x = jnp.pad(x, pads)
        return x

    def tile_geometry(self, guard_q_pad: bool) -> dict:
        tq, tk = self.tiles
        return dict(
            sq=self.sq, skv=self.skv, sq_pad=self.sq_pad,
            skv_pad=self.skv_pad, block_q=self.block_q,
            block_k=self.block_k, tq=tq, tk=tk, guard_q_pad=guard_q_pad,
            window=self.window or None,
        )


def _plan(q, k, v, *, causal: bool, block_q: int, block_k: int,
          interpret: Optional[bool], n_heads: int, rope: int = 0,
          n_kv_heads: int = 0, window: int = 0,
          turn: Optional[Tuple[int, int, bool]] = None,
          select: bool = False, norm: Optional[float] = None) -> _Plan:
    packed = n_heads > 0
    if packed:
        b, sq, hd = q.shape
        h = n_heads
        d = hd // h
        h_kv = n_kv_heads or h
        dv = k.shape[2] // h - (d - rope) if rope else v.shape[2] // h_kv
        skv = k.shape[1]
    else:
        b, h, sq, d = q.shape
        h_kv = k.shape[1]
        dv = v.shape[3]
        skv = k.shape[2]
    kv_ratio = h // h_kv
    if interpret is None:
        interpret = _use_interpret()
    block_q = min(block_q, _round_up(sq, 8))
    block_k = min(block_k, _round_up(skv, 8))
    skv_pad = _round_up(skv, block_k)
    tiles = (block_q, block_k)
    if causal:
        tiles = (_compute_tile(block_q, interpret),
                 _compute_tile(block_k, interpret))
        block_k = _resident_kv(block_k, tiles[1], skv_pad)
    return _Plan(
        packed, b, h, d, sq, skv, block_q, block_k,
        _round_up(sq, block_q), skv_pad,
        _head_group(h, block_q, block_k, d, packed, dv, rope, kv_ratio),
        tiles, interpret, dv, rope, kv_ratio, window, turn, select, norm,
    )


def _geometry(q_offset, kv_offset, skv: int):
    """The kernels' three scalar operands: int32 ``[1, 1]`` each,
    ``q_offset``, ``kv_offset``, ``kv_len`` (XLA hands a ``[1, 1]``
    constant to the kernel as it is; a longer vector costs a copy per
    call)."""
    with jax.named_scope(_GLUE_SCOPE):
        return [
            jnp.asarray(x, jnp.int32).reshape(1, 1)
            for x in (q_offset, kv_offset, skv)
        ]


def _last_kv_block(qi, geom, p: _Plan):
    """Index of the last K/V block that q block ``qi`` of a causal call
    can see (``geom``: the scalar-prefetch refs, as index maps get them).  Clamping a skipped step's K/V index map to it repeats the
    previous step's block, which the pipeline does not fetch again."""
    seen = geom[0][0, 0] + (qi + 1) * p.block_q - 1 - geom[1][0, 0]
    return jnp.clip(seen, 0, p.skv_pad - 1) // p.block_k


def _first_q_block(kj, geom, p: _Plan):
    """Index of the first q block that sees K/V block ``kj`` of a causal
    call (the dK/dV kernel's skipped steps come first)."""
    before = geom[1][0, 0] + kj * p.block_k - geom[0][0, 0]
    return jnp.clip(before, 0, p.sq_pad - 1) // p.block_q


def _first_kv_block(qi, geom, p: _Plan):
    """Index of the first K/V block that q block ``qi`` of a windowed call
    can see: ``_last_kv_block``'s twin at the band's lower edge.  Step 0 of
    the K/V grid axis is this block, so the blocks left of the band get
    neither a copy nor a grid step."""
    lowest = geom[0][0, 0] + qi * p.block_q - (p.window - 1) - geom[1][0, 0]
    return jnp.clip(lowest, 0, p.skv_pad - 1) // p.block_k


def _last_q_block(kj, geom, p: _Plan):
    """Index of the last q block that sees K/V block ``kj`` of a windowed
    call: ``_first_q_block``'s twin at the band's lower edge."""
    highest = (geom[1][0, 0] + (kj + 1) * p.block_k - 1 + (p.window - 1)
               - geom[0][0, 0])
    return jnp.clip(highest, 0, p.sq_pad - 1) // p.block_q


def _vspec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=_VMEM)


def _kind_params(p: _Plan) -> dict:
    """The kernels' static parameters that a grouped, windowed or masked
    call sets."""
    return dict(kv_shared=p.kv_ratio > 1, band=p if p.window else None,
                select=p.select)


def _compiler_params(p: _Plan):
    semantics = ("parallel", "parallel", "parallel", "arbitrary")
    if p.kv_ratio > 1:
        return pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_GROUPED_VMEM_LIMIT,
        )
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _kernel_name(base: str, p: _Plan) -> str:
    """A windowed call's kernels carry ``_window`` after the name every
    call's carry, so a model's window and full layers are told apart in a
    device trace and a reader that asks by prefix finds both; a call handed
    a mask (``keep=``) carries ``_select`` last."""
    base = base + "_window" if p.window else base
    return base + "_select" if p.select else base


def _grid_spec(causal: bool, *, grid, in_specs, out_specs, scratch_shapes):
    """Grid spec of a flash kernel whose first three operands are
    ``_geometry``'s.  Causal: scalar prefetch, so the index maps can read
    the offsets and clamp a skipped step to a block already there (they
    get the three refs as last arguments).  Else SMEM inputs like any
    other: the index maps need nothing of them, and the compiled step
    stays what it was before the kernels knew tiles."""
    if causal:
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes,
        )
    scalar = pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=_SMEM)
    return pl.GridSpec(
        grid=grid, in_specs=[scalar] * 3 + list(in_specs),
        out_specs=out_specs, scratch_shapes=scratch_shapes,
    )


def _keep_blocks(keep, p: "_Plan"):
    """The mask, ``[B, Skv, Sq]`` int8, padded with zeros to the blocks."""
    if (p.skv_pad, p.sq_pad) != (p.skv, p.sq):
        keep = jnp.pad(
            keep, ((0, 0), (0, p.skv_pad - p.skv), (0, p.sq_pad - p.sq))
        )
    return keep


def _rot_rows(rot, p: "_Plan"):
    """The rotation's ``[Sq, 2 r]`` table padded to the q blocks."""
    if p.sq_pad != p.sq:
        rot = jnp.pad(rot, ((0, p.sq_pad - p.sq), (0, 0)))
    return rot


class _Static(NamedTuple):
    """What a flash call fixes when it is traced: ``_flash``'s one argument
    that is no operand.  ``static_offsets``: the two offsets where the
    caller gave Python ints, for the build-time tile counters only.
    ``rope``, ``n_kv_heads``, ``window``, ``turn``, ``norm``: ``_Plan``'s.
    ``return_q``: the call's third result is the q its scores saw."""

    sm_scale: float
    causal: bool
    block_q: int
    block_k: int
    interpret: Optional[bool]
    n_heads: int = 0
    static_offsets: Optional[Tuple[int, int]] = None
    rope: int = 0
    n_kv_heads: int = 0
    window: int = 0
    turn: Optional[Tuple[int, int, bool]] = None
    norm: Optional[float] = None
    return_q: bool = False

    def plan(self, q, k, v, keep) -> _Plan:
        return _plan(
            q, k, v, causal=self.causal, block_q=self.block_q,
            block_k=self.block_k, interpret=self.interpret,
            n_heads=self.n_heads, rope=self.rope,
            n_kv_heads=self.n_kv_heads, window=self.window, turn=self.turn,
            select=keep is not None, norm=self.norm,
        )


def _fwd_pallas(q, k, v, q_offset, kv_offset, rot, keep, scale,
                st: _Static):
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
    ran at ~43%% of peak (measured before PR 1; the record went in PR 30).

    ``rope`` (packed mode only): k is the packed ``kv`` ``[B,Skv,H*(n+Dv)]``
    and v the shared key ``[B,Skv,rope]`` (``_k_head``).

    ``n_kv_heads`` (packed mode; head-major K/V carry their own head
    axis): k/v hold that many heads, each shared by ``H / n_kv_heads``
    query heads.  ``window``: see :func:`flash_attention_with_lse`.

    ``rot`` / ``turn`` ("Rotary at the door") and ``scale`` / ``norm``
    ("Norm at the door"): q is unrotated / the projection's output, and a
    third result is the q the scores saw, shaped like q: the backward's
    residual.

    ``keep``: see :func:`flash_attention_with_lse`.
    """
    p = st.plan(q, k, v, keep)
    if st.causal:
        _book_tiles(st.static_offsets, **p.tile_geometry(guard_q_pad=False))
    if p.dv != p.d:
        _registry.always().counter("flash.calls.split_widths").inc()
    _book_call_kinds(p, 1)
    return _flash_fwd_call(
        q, k, v, _geometry(q_offset, kv_offset, p.skv), rot, keep, scale,
        p=p, sm_scale=st.sm_scale, causal=st.causal,
    )


# The two calls below are jitted so that a model's layers share one trace
# and one lowering of each kernel: JAX caches neither for a bare
# ``pallas_call``, and twelve layers would trace and lower the head-
# unrolled bodies 36 times (XLA inlines the calls; the compiled step is
# the same).
@functools.partial(
    jax.jit, static_argnames=("p", "sm_scale", "causal"), inline=True
)
def _flash_fwd_call(q, k, v, geom, rot=None, keep=None, scale=None, *,
                    p: _Plan, sm_scale: float, causal: bool):
    b, h, d, dv, group, rope = p.b, p.h, p.d, p.dv, p.group, p.rope
    block_q, block_k, sq_pad, skv_pad = (
        p.block_q, p.block_k, p.sq_pad, p.skv_pad
    )
    with jax.named_scope(_GLUE_SCOPE):
        qr = p.pad_seq(q, p.sq, sq_pad)
        kr = p.pad_seq(k, p.skv, skv_pad)
        vr = p.pad_seq(v, p.skv, skv_pad)
        turned = [] if p.turn is None else [_rot_rows(rot, p)]
        normed = [] if p.norm is None else [scale]
        kept = [] if keep is None else [_keep_blocks(keep, p)]
        seen = [jax.ShapeDtypeStruct(qr.shape, qr.dtype)] if p.door else []

    def kv_block(qi, kj, geom):
        if not causal:
            return kj
        if p.window:
            kj = kj + _first_kv_block(qi, geom, p)
        return jnp.minimum(kj, _last_kv_block(qi, geom, p))

    def kv_head(hi):
        return hi if p.kv_ratio == 1 else hi // p.subs

    def q_side(width):
        if p.packed:
            return _vspec(
                (1, block_q, group * width),
                lambda bi, hi, qi, kj, *geom: (bi, qi, hi),
            )
        return _vspec(
            (1, group, block_q, width),
            lambda bi, hi, qi, kj, *geom: (bi, hi, qi, 0),
        )

    def kv_side(width, shared=False):
        if p.packed:
            return _vspec(
                (1, block_k, width if shared else p.kv_group * width),
                lambda bi, hi, qi, kj, *geom: (
                    bi, kv_block(qi, kj, geom), 0 if shared else kv_head(hi)),
            )
        return _vspec(
            (1, p.kv_group, block_k, width),
            lambda bi, hi, qi, kj, *geom: (
                bi, kv_head(hi), kv_block(qi, kj, geom), 0),
        )

    o_shape = jax.ShapeDtypeStruct(
        (b, sq_pad, h * dv) if p.packed else (b, h, sq_pad, dv), q.dtype
    )

    out, lse, *qt = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal,
            masked=causal or skv_pad != p.skv, tiles=p.tiles,
            packed=p.packed, d=d, dv=dv, rope=rope, **_kind_params(p),
            turn=p.turn, norm=p.norm,
        ),
        grid_spec=_grid_spec(
            causal,
            grid=(b, h // group, sq_pad // block_q, p.kv_steps),
            in_specs=[q_side(d)] + (
                [kv_side(d - rope + dv), kv_side(rope, shared=True)] if rope
                else [kv_side(d), kv_side(dv)]
            ) + [
                _vspec(
                    (block_q, x.shape[1]),
                    lambda bi, hi, qi, kj, *geom: (qi, 0),
                ) for x in turned
            ] + [_vspec(x.shape, lambda *_: (0, 0)) for x in normed] + [
                _vspec(
                    (1, block_k, block_q),
                    lambda bi, hi, qi, kj, *geom: (
                        bi, kv_block(qi, kj, geom), qi),
                ) for _ in kept
            ],
            out_specs=[
                q_side(dv),
                _vspec(
                    (1, group, 8, block_q),
                    lambda bi, hi, qi, kj, *geom: (bi, hi, 0, qi),
                ),
            ] + [q_side(d) for _ in seen],
            scratch_shapes=[
                _VMEM((group, dv, block_q), jnp.float32),
                _VMEM((group, 1, block_q), jnp.float32),
                _VMEM((group, 1, block_q), jnp.float32),
            ],
        ),
        out_shape=[
            o_shape,
            jax.ShapeDtypeStruct((b, h, 8, sq_pad), jnp.float32),
        ] + seen,
        # batch/head/qi programs are independent; only the K/V stream (kj)
        # carries state — lets Mosaic parallelize/pipeline the outer grid.
        compiler_params=_compiler_params(p),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * sq_pad * skv_pad * (d + dv),
            bytes_accessed=(qr.size + kr.size + vr.size) * qr.dtype.itemsize
            + b * h * sq_pad * dv * qr.dtype.itemsize,
            transcendentals=b * h * sq_pad * skv_pad,
        ),
        interpret=p.interpret,
        name=_kernel_name("hvd_flash_fwd", p),
    )(*geom, qr, kr, vr, *turned, *normed, *kept)

    with jax.named_scope(_GLUE_SCOPE):
        if p.packed:
            out = out[:, :p.sq]  # [B,Sq,H*D]
            qt = [x[:, :p.sq] for x in qt]
        else:
            out = out[:, :, :p.sq]  # [B,H,Sq,D]
            qt = [x[:, :, :p.sq] for x in qt]
        lse = lse[:, :, 0, :p.sq]  # [B,H,Sq]
    return (out, lse, *qt)


# ---------------------------------------------------------------------------
# Backward: two Pallas kernels recomputing p from the saved lse (the flash
# residual trick).  dk/dv streams Q blocks per K tile; dq streams K tiles
# per Q block.  Standard flash gradients, plus the ``g_lse`` term (``lse``
# receives real cotangents through ring attention's combine weights):
#     p  = exp(s - lse)           (masked)
#     ds = p ⊙ (dP − Δ'),   Δ' = rowsum(g ⊙ out) − g_lse
#     dq = ds·K·scale, dk = dsᵀ·Q·scale, dv = pᵀ·g
# The row statistic is made at dQ's door ("Δ at the door"): dQ, which holds
# a q block's ``g`` in VMEM anyway, reads ``out``'s block beside it and on
# the block's first K/V step takes ``Δ'`` a head in float32 (``_delta_rows``),
# keeps it in its second OUTPUT block for its own tiles and writes it back in
# the row statistics' layout; dK/dV runs after dQ and reads it there.  No
# ``rowsum(g ⊙ out)`` is XLA's, so no float32 ``g`` or ``out`` exists
# outside VMEM, and a score tile subtracts ONE vector.
# Causal calls walk each block pair in the forward's compute tiles and
# classes; both kernels take a q tile and loop over its K/V tiles (the
# dk/dv accumulators do not care in which order their tiles are met).
#
# Both kernels hold their score tile keys-by-queries like the forward,
# ``sᵀ = K·Qᵀ`` and ``dPᵀ = V·gᵀ`` as ``[cols, rows]``: the row statistics
# (``lse``, ``Δ'``), stored with the rows on lanes, are ``[1, rows]`` reads
# that broadcast over sublanes as they lie.
#
# All three accumulating products stream their THIN operand and leave the
# score-sized one standing in the MXU: dk/dv accumulates ``dvᵀ = gᵀ·p`` and
# ``dkᵀ = Qᵀ·ds`` as ``[d, cols]`` (left operand the ``[rows, d]`` g / Q
# tile, contracted on its rows; right operand ``pᵀ`` / ``dsᵀ`` as they
# lie, contracted on theirs), dq accumulates ``dqᵀ = Kᵀ·dsᵀ`` as
# ``[d, rows]``.  The MXU takes a transposed right operand for nothing and
# a transposed left operand not at all, so what Mosaic turns on the XLU is
# the thin tile, never ``p`` or ``ds``, the kernels' largest arrays; each
# accumulator is turned back once per head where its block is written.
# At d 64 the other way round (``pᵀ·g``, ``[cols, rows] x [rows, 64]``)
# streamed every row of the scores through passes whose output filled 64
# of the MXU's 128 columns: dk/dv 815 -> 757 us a layer at s 1024 causal,
# 718 -> 524 at s 512 (PERF.md, PR 34).  With heads of 128 that pass is
# full, and dk/dv keeps ``pᵀ·g`` / ``dsᵀ·Q`` into ``[cols, d]``
# (``_dkv_streams_thin``).  Same mathematics and dtypes as queries-by-keys.
# ---------------------------------------------------------------------------


def _dkv_streams_thin(d: int) -> bool:
    """Whether dK/dV's two accumulating matmuls stream the thin
    ``[rows, d]`` operand (``dvᵀ = gᵀ·p``, ``dkᵀ = Qᵀ·ds``, accumulators
    ``[d, cols]``) and leave ``pᵀ`` / ``dsᵀ`` standing in the MXU: where
    a head is narrower than the 128 lanes, so that ``pᵀ·g`` would stream
    every row of the scores through passes whose output fills ``d`` of 128
    columns.  At ``d`` 128 that pass is full as it is and the turned form
    only adds stationary-operand loads: measured there 474 against 431 us a
    layer at s 1024 causal and 290 against 290 at s 512 (PERF.md, PR 34).
    Static, from a shape the kernel already has; counted at build time as
    ``flash.dkv.thin_streamed``."""
    return d < _LANES


def _delta_rows(g_ref, out_ref, glse_ref, delta_ref, *, group: int, dv: int,
                packed: bool):
    """``Δ' = rowsum(g ⊙ out) − g_lse`` of a q block into delta_ref, a head
    a ``[1, block_q]`` row as the statistics lie (written to every sublane
    of the head's ``[8, block_q]``): float32 products, turned so that the
    float32 sum runs over sublanes and the rows land on the lanes.  What is
    turned is a vreg's 128 lanes where the heads fill them whole: the heads
    of a packed block that share a lane tile (two of 64) go through one
    turn and no lane shift, and a head wider than the lanes adds its lane
    tiles up first; any other width is turned a head at a time."""
    for at, heads in _lane_views(packed, group):
        width = len(heads) * dv
        shared = dv < _LANES and _LANES % dv == 0 and width % _LANES == 0
        span = _LANES if shared else dv
        for a in range(0, width, span):
            lanes = (*at, slice(None), slice(a, a + span))
            products = (
                g_ref[lanes].astype(jnp.float32)
                * out_ref[lanes].astype(jnp.float32)
            )  # [block_q, span]
            if span > _LANES and span % _LANES == 0:
                products = functools.reduce(jnp.add, [
                    products[:, t:t + _LANES]
                    for t in range(0, span, _LANES)
                ])
            turned = products.T  # [lanes, block_q]
            for i in range(span // dv):
                g = heads[a // dv + i]
                head = turned[i * dv:(i + 1) * dv] if shared else turned
                row = jnp.sum(head, axis=0, keepdims=True)
                delta_ref[0, g] = jnp.broadcast_to(
                    row - glse_ref[0, g, 0:1, :], delta_ref.shape[2:]
                )


def _recompute_p_ds(lse_ref, delta_ref, q_ref, k_ref, v_ref,
                    g_ref, g, rq, rk, valid, *, sm_scale: float,
                    packed: bool = False, d: int = 0, dv: int = 0,
                    rope: int = 0, kv_shared: bool = False):
    """Shared per-(q rows ``rq``, K/V rows ``rk``, head) recompute:
    returns (pᵀ, dsᵀ, q_blk, g_blk, k_blk), the two score-sized arrays
    keys-by-queries, ``[cols, rows]``, like ``valid`` (``None`` is the
    unmasked path); the row statistics are read as stored, ``[1, rows]``
    (delta_ref holds ``Δ'``, ``g_lse`` inside it).

    Padded / fully-masked Q rows carry ``lse == -inf`` and zero ``g``;
    ``row_ok`` zeroes their ``p`` so they contribute nothing (a tile that
    holds such rows is never classed interior).
    """
    # Storage-dtype (bf16) matmul inputs with fp32 accumulation — see the
    # forward kernel note; only the softmax/ds algebra runs in fp32.
    heads = dict(packed=packed, d=d, dv=dv, rope=rope, kv_shared=kv_shared)
    q_blk = _head(q_ref, g, d, packed, rq)
    g_blk = _head(g_ref, g, dv, packed, rq)
    k_blk = _k_head(k_ref, v_ref, g, rk, **heads)
    v_blk = _v_head(k_ref, v_ref, g, rk, **heads)

    s_t = jax.lax.dot_general(
        k_blk,
        q_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # [cols, rows] fp32

    lse_row = lse_ref[0, g, 0:1, rq]  # [1, rows]
    if valid is not None:
        row_ok = lse_row > _NEG_INF / 4  # -inf rows: no valid keys anywhere
        lse_safe = jnp.where(row_ok, lse_row, 0.0)
        p_t = jnp.where(
            jnp.logical_and(valid, row_ok), jnp.exp(s_t - lse_safe), 0.0
        )
    else:
        p_t = jnp.exp(s_t - lse_row)

    dp_t = jax.lax.dot_general(
        v_blk,
        g_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds_t = p_t * (dp_t - delta_ref[0, g, 0:1, rq])
    return p_t, ds_t, q_blk, g_blk, k_blk


def _bwd_kernel_dkdv(
    qoff_ref, kvoff_ref, kvlen_ref, lse_ref, delta_ref,
    q_ref, k_ref, v_ref, g_ref, *refs,
    sm_scale: float, causal: bool, masked: bool, tiles: Tuple[int, int],
    q_len: int, packed: bool = False, d: int = 0, dv: int = 0,
    rope: int = 0, kv_shared: bool = False, q_steps: int = 0,
    band: Optional[_Plan] = None, select: bool = False,
):
    """grid (b, h-group, kj, qi): each K tile accumulates over streamed
    Q blocks; the per-head loop is a static unroll (see forward).  Heads
    narrower than the lanes stream their thin operand
    (:func:`_dkv_streams_thin`): the accumulators are ``dkᵀ`` / ``dvᵀ``,
    ``[G, d, block_k]``, the K/V rows on lanes (a causal slab is a static
    lane slice from 0, a whole number of K/V tiles), turned back once per
    head where the K/V block is written.  Else ``[G, block_k, d]``.  Each
    accumulator takes its form from its own width (dK's ``d``, dV's
    ``dv``).

    With ``rope`` (``_k_head``) dk_ref is the ``[dk_nope | dv]`` block in
    ``kv``'s own layout, written by head where each accumulator ends, and
    dK's accumulator is ``d - rope`` wide; what the shared key receives
    from the group's heads adds up, in float32, in one more accumulator of
    width ``rope`` (``shared_acc``), and dv_ref, ``[1, 1, rope, block_k]``
    float32, takes that partial sum: one a head group, the groups summed
    outside.

    With ``kv_shared`` (query groups) the K/V block, dk_ref, dv_ref and the
    two accumulators are ONE K/V head's, and what the program's query heads
    give it adds up in the accumulators.  Where a K/V head's query heads
    take several programs (``q_steps`` > 0: the q blocks a program streams)
    they follow one another along the last grid axis, ``(heads' program, q
    block)`` merged, and the accumulators run on through all of them.  With
    ``band`` (windowed) step 0 of a program's q blocks is the K/V block's
    first (``_first_q_block``) and the axis ends with the band.  delta_ref
    is dQ's ``Δ'`` ("Δ at the door").  The refs after g_ref are, with
    ``select``, the mask's block (``_drive_tiles``), then ``dk_ref, dv_ref,
    dk_acc, dv_acc`` and ``shared_acc``."""
    refs = list(refs)
    keep_ref = refs.pop(0) if select else None
    dk_ref, dv_ref, dk_acc, dv_acc, *shared_acc = refs
    shared_acc = tuple(shared_acc)
    n = d - rope
    qi = step = pl.program_id(3)
    kj = pl.program_id(2)
    nq = pl.num_programs(3)
    geom = (qoff_ref[0, 0], kvoff_ref[0, 0], kvlen_ref[0, 0])
    group, block_q, block_k = _block_dims(q_ref, k_ref, packed, d)
    if q_steps:
        qi = lax.rem(step, q_steps)
    if band is not None:
        qi = qi + _first_q_block(kj, (qoff_ref, kvoff_ref, kvlen_ref), band)

    @pl.when(step == 0)
    def _init():
        for acc in (dk_acc, dv_acc) + shared_acc:
            acc[:, :, :] = jnp.zeros_like(acc)

    def accumulate(acc, g, rk, scores_t, blk, scale=None):
        """Adds ``blkᵀ·scores`` as ``[width, cols]`` (the ``[rows, width]``
        g / Q tile streamed and turned, ``pᵀ`` / ``dsᵀ`` standing as the
        right operand), or ``scores_t·blk`` as ``[cols, width]``; fp32.
        Returns the scores as the matmul took them, for a second use."""
        thin = _dkv_streams_thin(blk.shape[1])
        at = (g, slice(None), rk) if thin else (g, rk, slice(None))
        so_far = acc[at]  # read before the product, as the kernel always did
        scores_t = scores_t.astype(blk.dtype)
        lhs, rhs, contract = (
            (blk, scores_t, ((0,), (1,))) if thin
            else (scores_t, blk, ((1,), (0,)))
        )
        product = jax.lax.dot_general(
            lhs, rhs, dimension_numbers=(contract, ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[at] = so_far + (product if scale is None else product * scale)
        return scores_t

    def update(rq, rk, valid):
        for g in range(group):
            p_t, ds_t, q_blk, g_blk, _ = _recompute_p_ds(
                lse_ref, delta_ref, q_ref, k_ref, v_ref, g_ref,
                g, rq, rk, valid, sm_scale=sm_scale, packed=packed, d=d,
                dv=dv, rope=rope, kv_shared=kv_shared,
            )
            to = 0 if kv_shared else g  # the accumulators' head
            accumulate(dv_acc, to, rk, p_t, g_blk)
            ds_t = accumulate(
                dk_acc, to, rk, ds_t, q_blk[:, :n] if rope else q_blk,
                sm_scale,
            )
            if rope:
                accumulate(shared_acc[0], 0, rk, ds_t, q_blk[:, n:], sm_scale)

    _in_band(band, qi, band and band.sq_pad // block_q)(lambda: _drive_tiles(
        update, geom, qi, kj, q_len=q_len, block_q=block_q, block_k=block_k,
        causal=causal, masked=masked, tiles=tiles,
        window=band and band.window, keep_ref=keep_ref,
    ))

    def grad(acc, g, width):
        """Head ``g`` of an accumulator as ``[block_k, width]``."""
        out = acc[g, :, :]
        return out.T if _dkv_streams_thin(width) else out

    @pl.when(step == nq - 1)
    def _finalize():
        for g in range(1 if kv_shared else group):
            if rope:  # [dk_nope | dv] side by side, as kv holds the head
                head = g * (n + dv)
                for acc, lo, width in ((dk_acc, head, n), (dv_acc, head + n, dv)):
                    dk_ref[0, :, lo:lo + width] = grad(acc, g, width).astype(
                        dk_ref.dtype
                    )
                continue
            for ref, acc, width in ((dk_ref, dk_acc, d), (dv_ref, dv_acc, dv)):
                _head_store(
                    ref, g, width, packed,
                    grad(acc, g, width).astype(ref.dtype),
                )
        if rope:  # rows on the lanes, as the thin form holds it
            shared = shared_acc[0][0, :, :]
            dv_ref[0, 0] = shared if _dkv_streams_thin(rope) else shared.T


def _bwd_kernel_dq(
    qoff_ref, kvoff_ref, kvlen_ref, lse_ref, glse_ref,
    q_ref, k_ref, v_ref, g_ref, out_ref, *refs,
    sm_scale: float, causal: bool, masked: bool, tiles: Tuple[int, int],
    q_len: int, packed: bool = False, d: int = 0, dv: int = 0,
    rope: int = 0, kv_shared: bool = False, band: Optional[_Plan] = None,
    turn: Optional[Tuple[int, int, bool]] = None, select: bool = False,
    norm: Optional[float] = None,
):
    """grid (b, h-group, qi, kj): each Q block accumulates over streamed
    K tiles, as ``dqᵀ``, ``[G, d, block_q]``; the per-head loop is a
    static unroll (see forward).  ``kv_shared`` / ``band``: as the
    forward.  out_ref is the forward's out block, laid like g_ref; the
    block's first step takes ``Δ'`` from the two ("Δ at the door",
    ``_delta_rows``) into delta_ref, the output behind dq_ref,
    ``[1, G, 8, block_q]`` like lse_ref, which the tiles read and dK/dV
    reads after them.  With ``turn`` ("Rotary at the door") q_ref holds the
    turned q the forward wrote, the refs after out_ref are ``rot_ref,
    dq_ref, delta_ref, dq_acc``, and each head's gradient is turned back
    where it is written: dq_ref takes the gradient of the unrotated q.
    With ``norm`` ("Norm at the door") ``raw_ref, scale_ref`` come behind
    rot_ref, the projection's q block and the ``[1, d]`` scale, and
    ``dscale_ref`` behind delta_ref: the gradient, turned back, goes
    through the norm's backward where it is written, and dscale_ref, ``[1,
    1, 1, 1, d]`` float32, takes the program's part of the scale's.  With
    ``select`` the mask's block comes before dq_ref."""
    refs = list(refs)
    rot_ref = refs.pop(0) if turn is not None else None
    raw_ref, scale_ref = (
        (refs.pop(0), refs.pop(0)) if norm is not None else (None, None)
    )
    keep_ref = refs.pop(0) if select else None
    dq_ref, delta_ref, *dscale_ref, dq_acc = refs
    qi = pl.program_id(2)
    kj = step = pl.program_id(3)
    nk = pl.num_programs(3)
    geom = (qoff_ref[0, 0], kvoff_ref[0, 0], kvlen_ref[0, 0])
    group, block_q, block_k = _block_dims(q_ref, k_ref, packed, d)
    if band is not None:
        kj = step + _first_kv_block(
            qi, (qoff_ref, kvoff_ref, kvlen_ref), band
        )

    @pl.when(step == 0)
    def _init():
        dq_acc[:, :, :] = jnp.zeros_like(dq_acc)
        _delta_rows(
            g_ref, out_ref, glse_ref, delta_ref, group=group, dv=dv,
            packed=packed,
        )

    def update(rq, rk, valid):
        for g in range(group):
            _, ds_t, _, _, k_blk = _recompute_p_ds(
                lse_ref, delta_ref, q_ref, k_ref, v_ref, g_ref,
                g, rq, rk, valid, sm_scale=sm_scale, packed=packed, d=d,
                dv=dv, rope=rope, kv_shared=kv_shared,
            )
            dq_acc[g, :, rq] = dq_acc[g, :, rq] + jax.lax.dot_general(
                k_blk, ds_t.astype(k_blk.dtype),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [d, rows]

    _in_band(band, kj, band and band.skv_pad // block_k)(lambda: _drive_tiles(
        update, geom, qi, kj, q_len=q_len, block_q=block_q, block_k=block_k,
        causal=causal, masked=masked, tiles=tiles,
        window=band and band.window, keep_ref=keep_ref,
    ))

    @pl.when(step == nk - 1)
    def _finalize():
        if turn is None and norm is None:
            for g in range(group):
                _head_store(
                    dq_ref, g, d, packed,
                    dq_acc[g, :, :].T.astype(dq_ref.dtype),
                )
            return
        # the accumulators' rows are the block's lanes: turned tile by tile
        # as they are transposed, a tile's rows from one head or from two
        dscale = 0.0
        for at, heads in _lane_views(packed, group):
            def rows(a, b, heads=heads):
                """Lanes ``[a, b)`` of the heads side by side, as
                ``[block_q, b - a]``."""
                pieces = [
                    dq_acc[g, max(a - i * d, 0):min(b - i * d, d), :]
                    for i, g in enumerate(heads) if i * d < b and a < (i + 1) * d
                ]
                return (pieces[0] if len(pieces) == 1
                        else jnp.concatenate(pieces, axis=0)).T

            def put(a, b, x, at=at):
                dq_ref[(*at, slice(None), slice(a, b))] = x.astype(
                    dq_ref.dtype
                )

            # gz: the gradient of the normed q, float32 lanes kept until
            # each head's are whole
            gz = {}

            def hold(a, b, x, gz=gz):
                gz[a, b] = x

            through = put if norm is None else hold
            head_lanes = [(i * d, (i + 1) * d) for i in range(len(heads))]
            if turn is None:
                for a, b in head_lanes:
                    through(a, b, rows(a, b))
            else:
                _turn_lanes(
                    rows, through, dq_ref.shape[-1], len(heads), d,
                    rot_ref[...], turn, back=True,
                )
            if norm is None:
                continue
            scale = scale_ref[...]
            for a, b in head_lanes:
                g_z = _lanes_of(gz, a, b)
                x = raw_ref[(*at, slice(None), slice(a, b))].astype(
                    jnp.float32
                )
                r = _inv_rms(x, norm)
                y = x * r
                g_y = g_z * scale
                put(a, b, r * (
                    g_y - y * jnp.mean(g_y * y, axis=1, keepdims=True)
                ))
                dscale = dscale + jnp.sum(g_z * y, axis=0, keepdims=True)
        if norm is not None:
            dscale_ref[0][0, 0, 0] = dscale


def _bwd_pallas(q, k, v, q_offset, kv_offset, out, lse, g_out, g_lse, rot,
                keep, raw, scale, st: _Static):
    """``(dq, dk, dv)``.  With ``rot`` / ``turn`` ("Rotary at the door")
    ``q`` is the forward's turned q and ``dq`` the gradient of the
    unrotated one.  With ``scale`` / ``norm`` ("Norm at the door") ``q`` is
    the forward's normed q, ``raw`` the projection's output, ``dq`` its
    gradient, and the scale's gradient a fourth result."""
    p = st.plan(q, k, v, keep)
    if st.causal:
        # one count for each of the two kernels
        for _ in range(2):
            _book_tiles(
                st.static_offsets, **p.tile_geometry(guard_q_pad=True)
            )
    # dK/dV's accumulators: dK's (with rope its own columns only), dV's
    # and, with rope, the shared key's
    accumulated = (p.d - p.rope, p.dv) + ((p.rope,) if p.rope else ())
    if any(_dkv_streams_thin(width) for width in accumulated):
        _registry.always().counter("flash.dkv.thin_streamed").inc()
    _book_call_kinds(p, 2, backward=True)
    return _flash_bwd_call(
        q, k, v, _geometry(q_offset, kv_offset, p.skv), out, lse, g_out,
        g_lse, rot, keep, raw, scale, p=p, sm_scale=st.sm_scale,
        causal=st.causal,
    )


@functools.partial(
    jax.jit, static_argnames=("p", "sm_scale", "causal"), inline=True
)
def _flash_bwd_call(q, k, v, geom, out, lse, g_out, g_lse, rot=None,
                    keep=None, raw=None, scale=None, *, p: _Plan,
                    sm_scale: float, causal: bool):
    b, h, d, dv, group, sq, skv = p.b, p.h, p.d, p.dv, p.group, p.sq, p.skv
    rope, n = p.rope, p.d - p.rope
    block_q, block_k, sq_pad, skv_pad = (
        p.block_q, p.block_k, p.sq_pad, p.skv_pad
    )
    with jax.named_scope(_GLUE_SCOPE):
        qr = p.pad_seq(q, sq, sq_pad)
        kr = p.pad_seq(k, skv, skv_pad)
        vr = p.pad_seq(v, skv, skv_pad)
        gr = p.pad_seq(g_out.astype(q.dtype), sq, sq_pad)
        outr = p.pad_seq(out, sq, sq_pad)

        # Row statistics in the kernel's [b, h, 8, sq_pad] layout (8 = min
        # sublane tile; kernels read sublane 0).
        def rows(x, pad_value):
            x = x.reshape(b, h, sq)
            if sq_pad != sq:
                x = jnp.pad(x, ((0, 0), (0, 0), (0, sq_pad - sq)),
                            constant_values=pad_value)
            return jnp.broadcast_to(x[:, :, None, :], (b, h, 8, sq_pad))

        lse_rows = rows(lse, -jnp.inf)  # padded rows masked via row_ok
        glse = jnp.zeros((b, h, sq), jnp.float32) if g_lse is None else g_lse
        glse_rows = rows(glse.astype(jnp.float32), 0.0)
        turned = [] if p.turn is None else [_rot_rows(rot, p)]
        normed = [] if p.norm is None else [p.pad_seq(raw, sq, sq_pad), scale]
        kept = [] if keep is None else [_keep_blocks(keep, p)]

    kernel_params = dict(
        sm_scale=sm_scale, causal=causal,
        masked=causal or skv_pad != skv or sq_pad != sq,
        tiles=p.tiles, q_len=sq, packed=p.packed, d=d, dv=dv, rope=rope,
        **_kind_params(p),
    )
    call_params = dict(
        compiler_params=_compiler_params(p),
        interpret=p.interpret,
    )
    def specs(order):
        """(row-statistics, q, k, v, g, rotation, mask) block specs for a grid whose last
        two axes are ``order``: "kq" (dK/dV: q streams innermost, its
        skipped steps clamped to the first q block needed) or "qk" (dQ:
        K/V streams innermost, clamped to the last K/V block needed).
        q and k blocks are ``d`` wide, v and g blocks ``dv`` (and dQ's out
        block, which takes g's spec); with ``rope`` the k block is the
        packed ``kv``'s and the v block the shared key's."""

        def blocks(i, j, geom):
            qi, kj = (j, i) if order == "kq" else (i, j)
            if order == "kq" and p.subs > 1:
                qi = lax.rem(qi, p.q_steps)
            if causal and order == "kq" and p.window:
                qi = jnp.minimum(qi + _first_q_block(kj, geom, p),
                                 _last_q_block(kj, geom, p))
            elif causal and order == "kq":
                qi = jnp.maximum(qi, _first_q_block(kj, geom, p))
            elif causal:
                if p.window:
                    kj = kj + _first_kv_block(qi, geom, p)
                kj = jnp.minimum(kj, _last_kv_block(qi, geom, p))
            return qi, kj

        def heads(hi, j):
            """(query heads' program, K/V head or group) of a grid step
            whose second index is ``hi``: the same unless query heads
            share K/V heads; dK/dV's grid then runs over the K/V heads and
            a head's programs follow one another along its last axis."""
            if p.kv_ratio == 1 or p.subs == 1:
                return hi, hi
            if order == "kq":
                return hi * p.subs + j // p.q_steps, hi
            return hi, hi // p.subs

        def q_map(bi, hi, i, j, *geom):
            qi, _ = blocks(i, j, geom)
            hi, _ = heads(hi, j)
            return (bi, qi, hi) if p.packed else (bi, hi, qi, 0)

        def kv_map(bi, hi, i, j, *geom):
            _, kj = blocks(i, j, geom)
            _, hi = heads(hi, j)
            return (bi, kj, hi) if p.packed else (bi, hi, kj, 0)

        def stat_map(bi, hi, i, j, *geom):
            qi, _ = blocks(i, j, geom)
            hi, _ = heads(hi, j)
            return (bi, hi, 0, qi)

        def shared_map(bi, hi, i, j, *geom):
            _, kj = blocks(i, j, geom)
            return (bi, kj, 0)

        def rot_map(bi, hi, i, j, *geom):
            return (blocks(i, j, geom)[0], 0)

        def keep_map(bi, hi, i, j, *geom):
            qi, kj = blocks(i, j, geom)
            return (bi, kj, qi)

        def block(rows, width, index_map, heads=group):
            return _vspec(
                (1, rows, heads * width) if p.packed
                else (1, heads, rows, width), index_map,
            )

        return (
            _vspec((1, group, 8, block_q), stat_map),
            block(block_q, d, q_map),
            block(block_k, n + dv if rope else d, kv_map, p.kv_group),
            _vspec((1, block_k, rope), shared_map) if rope
            else block(block_k, dv, kv_map, p.kv_group),
            block(block_q, dv, q_map),
            [_vspec((block_q, x.shape[1]), rot_map) for x in turned],
            [_vspec((1, block_k, block_q), keep_map) for _ in kept],
        )

    def shape_like(x, s_pad, width, heads=h):
        return jax.ShapeDtypeStruct(
            (b, s_pad, heads * width) if p.packed
            else (b, heads, s_pad, width), x.dtype,
        )

    # dq: grid (b, h-group, qi, kj) — k streams innermost.  It runs first:
    # its second result is ``Δ'`` in the statistics' layout, dK/dV's operand.
    stat_spec, q_spec, k_spec, v_spec, g_spec, rot_spec, keep_spec = specs(
        "qk"
    )
    dq_spec, norm_spec = [q_spec, stat_spec], []
    dq_shape = [
        shape_like(q, sq_pad, d),
        jax.ShapeDtypeStruct((b, h, 8, sq_pad), jnp.float32),
    ]
    if p.norm is not None:
        # the raw q block and the scale in; the scale's gradient out, one
        # float32 row a program
        norm_spec = [q_spec, _vspec(scale.shape, lambda *_: (0, 0))]
        programs = (b, h // group, sq_pad // block_q)
        dq_spec.append(_vspec(
            (1, 1, 1, 1, d), lambda bi, hi, qi, kj, *geom: (bi, hi, qi, 0, 0)
        ))
        dq_shape.append(jax.ShapeDtypeStruct(programs + (1, d), jnp.float32))
    dq, delta_rows, *dscale = pl.pallas_call(
        functools.partial(
            _bwd_kernel_dq, **kernel_params, turn=p.turn, norm=p.norm
        ),
        grid_spec=_grid_spec(
            causal,
            grid=(b, h // group, sq_pad // block_q, p.kv_steps),
            in_specs=[stat_spec, stat_spec,
                      q_spec, k_spec, v_spec, g_spec, g_spec] + rot_spec
            + norm_spec + keep_spec,
            out_specs=dq_spec,
            scratch_shapes=[_VMEM((group, d, block_q), jnp.float32)],
        ),
        out_shape=dq_shape,
        **call_params,
        name=_kernel_name("hvd_flash_bwd_dq", p),
    )(*geom, lse_rows, glse_rows, qr, kr, vr, gr, outr, *turned, *normed,
      *kept)

    # dk/dv: grid (b, h-group, kj, qi) — q streams innermost; ``Δ'`` is dQ's.
    stat_spec, q_spec, k_spec, v_spec, g_spec, _, keep_spec = specs("kq")

    def dkv_acc(width, heads=p.kv_group):
        return _VMEM(
            (heads, width, block_k) if _dkv_streams_thin(width)
            else (heads, block_k, width), jnp.float32,
        )

    h_kv = h // p.kv_ratio
    dkv_out_specs, dkv_out_shape = [k_spec, v_spec], [
        shape_like(k, skv_pad, d, h_kv), shape_like(v, skv_pad, dv, h_kv)
    ]
    if rope:
        # [dk_nope | dv] in kv's layout, and the shared key's gradient as
        # one float32 partial sum a head group, the K/V rows on the lanes
        dkv_out_specs[1] = _vspec(
            (1, 1, rope, block_k),
            lambda bi, hi, kj, qi, *geom: (bi, hi, 0, kj),
        )
        dkv_out_shape = [
            shape_like(k, skv_pad, n + dv),
            jax.ShapeDtypeStruct(
                (b, h // group, rope, skv_pad), jnp.float32
            ),
        ]
    grad_k, grad_v = pl.pallas_call(
        functools.partial(
            _bwd_kernel_dkdv, **kernel_params,
            q_steps=p.q_steps if p.subs > 1 else 0,
        ),
        grid_spec=_grid_spec(
            causal,
            grid=(b, h // group // p.subs, skv_pad // block_k,
                  p.subs * p.q_steps),
            in_specs=[stat_spec, stat_spec,
                      q_spec, k_spec, v_spec, g_spec] + keep_spec,
            out_specs=dkv_out_specs,
            scratch_shapes=[dkv_acc(n), dkv_acc(dv)] + (
                [dkv_acc(rope, 1)] if rope else []
            ),
        ),
        out_shape=dkv_out_shape,
        **call_params,
        name=_kernel_name("hvd_flash_bwd_dkv", p),
    )(*geom, lse_rows, delta_rows, qr, kr, vr, gr, *kept)
    if rope:
        with jax.named_scope(_GLUE_SCOPE):
            grad_v = grad_v.sum(axis=1).swapaxes(1, 2)

    with jax.named_scope(_GLUE_SCOPE):
        # the scale's gradient: [1, d], as the scale
        dscale = [x.sum(axis=(0, 1, 2)) for x in dscale]
        if p.packed:
            return (
                dq[:, :sq].astype(q.dtype),
                grad_k[:, :skv].astype(k.dtype),
                grad_v[:, :skv].astype(v.dtype),
                *dscale,
            )
        return (
            dq[:, :, :sq].astype(q.dtype),
            grad_k[:, :, :skv].astype(k.dtype),
            grad_v[:, :, :skv].astype(v.dtype),
            *dscale,
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _flash(q, k, v, q_offset, kv_offset, rot, keep, scale, st: _Static):
    """``(out, lse)`` with the exact backward.  With ``rope`` the operands
    ``k`` and ``v`` are the packed ``kv`` and the shared key (``_k_head``),
    and so are their cotangents.  ``rot`` (None, or with ``turn`` the
    rotation's table: "Rotary at the door"): q is unrotated, and so is its
    cotangent.  ``scale`` (None, or with ``norm`` the head-wise norm's
    float32 ``[1, d]`` scale: "Norm at the door"): q is the projection's
    output, and so is its cotangent; the scale takes its own.  ``keep``
    (None, or the int8 mask): a constant of the call.  With ``return_q`` a
    third result is the q the scores saw, a constant to the caller: what
    comes back for it is not read."""
    out, lse, *seen = _fwd_pallas(
        q, k, v, q_offset, kv_offset, rot, keep, scale, st
    )
    if st.return_q:  # the call's own q where the kernels wrote none
        return (out, lse, *seen, q)[:3]
    return out, lse


def _flash_fwd(q, k, v, q_offset, kv_offset, rot, keep, scale, st: _Static):
    if st.turn is None and st.norm is None:
        results = _flash(q, k, v, q_offset, kv_offset, rot, keep, scale, st)
        seen = q
    else:  # the q the scores saw is kept in q's place
        out, lse, seen = _fwd_pallas(
            q, k, v, q_offset, kv_offset, rot, keep, scale, st
        )
        results = (out, lse, seen) if st.return_q else (out, lse)
    raw = None if st.norm is None else q  # the norm's backward reads it
    return results, (
        seen, k, v, q_offset, kv_offset, rot, keep, raw, scale, *results[:2]
    )


def _flash_bwd(st: _Static, res, g):
    q, k, v, q_offset, kv_offset, rot, keep, raw, scale, out, lse = res
    g_out, g_lse = g[:2]
    dq, dk, dv, *dscale = _bwd_pallas(
        q, k, v, q_offset, kv_offset, out, lse, g_out, g_lse, rot, keep,
        raw, scale, st,
    )
    # Integer offsets and the int8 mask take float0 cotangents; the tables
    # are constants.
    zero = np.zeros((), dtype=jax.dtypes.float0)
    return (dq, dk, dv, zero, zero,
            None if rot is None else jnp.zeros_like(rot),
            None if keep is None else np.zeros(
                keep.shape, dtype=jax.dtypes.float0
            ),
            dscale[0] if dscale else None)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


class QRotary(NamedTuple):
    """``q_rotary=`` of the flash entries: q arrives as its projection
    leaves it and the kernels rotate it ("Rotary at the door").

    ``cos`` / ``sin``: float32 ``[Sq, r/2]``, the angles' cosines and sines
    for q's rows as they are passed (``models.transformer.rotary_tables``;
    numpy arrays stay constants of the program).  ``halves``: the pairs are
    ``(x[i], x[i + r/2])``, else adjacent, ``(x[2i], x[2i+1])``.  ``start``:
    the first rotated lane of a head; the ``r`` lanes from there turn, the
    others pass.  K is the caller's to rotate.  The function is that of
    ``rotary`` on those lanes followed by the same call without the
    argument; the cotangent of q is that of the unrotated q."""

    cos: Any
    sin: Any
    halves: bool = False
    start: int = 0


class QNorm(NamedTuple):
    """``q_norm=`` of the ``q, k, v`` entries: q arrives as its projection
    leaves it and the kernels norm each head of it ("Norm at the door").

    ``scale``: float32 ``[d]``, one learned scale over a head's ``d``
    columns, every head's alike (``models.transformer.RMSNorm`` on ``[B, S,
    H, d]``); ``eps``: a Python float.  The function is that of ``RMSNorm``
    on q's heads, then ``rotary`` where ``q_rotary`` is given too, followed
    by the same call without the argument, with the normed (and turned) q
    rounded to q's dtype once; the cotangent of q is that of the
    projection's output, and ``scale`` takes its own, in float32."""

    scale: Any
    eps: float = 1e-6


def _norm_operand(q_norm: QNorm, d: int):
    """``(scale, eps)`` of a :class:`QNorm` for q heads ``d`` wide: the
    kernels' float32 ``[1, d]`` operand and the static ``eps``."""
    scale, eps = q_norm
    if jnp.shape(scale) != (d,) or not isinstance(eps, (int, float)):
        raise ValueError(
            f"q_norm: scale {jnp.shape(scale)} has to be [head width {d}] "
            f"and eps a Python float (got {eps!r})"
        )
    with jax.named_scope(_GLUE_SCOPE):
        return jnp.asarray(scale, jnp.float32).reshape(1, d), float(eps)


def _rotary_operand(q_rotary: QRotary, sq: int, d: int, compiled: bool):
    """``(rot, turn)`` of a :class:`QRotary` for q heads ``d`` wide: the
    kernels' one float32 ``[Sq, 2 r]`` table, ``[cos | sin]`` spread over
    the ``r`` rotated lanes, each sine with the sign its lane takes (the
    first of a pair ``-``, the second ``+``), and the static ``(start, r,
    halves)``."""
    cos, sin, halves, start = q_rotary
    r = 2 * cos.shape[-1]
    if (cos.shape != (sq, r // 2) or sin.shape != cos.shape
            or start < 0 or start + r > d):
        raise ValueError(
            f"q_rotary: cos {cos.shape} / sin {sin.shape} have to be "
            f"[Sq={sq}, r/2] with start={start} + r <= head width {d}"
        )
    if not halves and (start % 2 or d % 2):
        raise ValueError(
            "q_rotary: adjacent pairs start on even lanes; got "
            f"start={start} in heads {d} wide"
        )
    if compiled and (start % 64 or r % 64):
        raise ValueError(
            "q_rotary needs start and the rotated width to be multiples of "
            f"64 on TPU (Mosaic lane slicing); got start={start}, r={r}"
        )
    xp = np if all(isinstance(x, np.ndarray) for x in (cos, sin)) else jnp
    with jax.named_scope(_GLUE_SCOPE):
        if halves:
            spread = [xp.concatenate(pair, axis=-1)
                      for pair in ((cos, cos), (-sin, sin))]
        else:
            spread = [xp.stack(pair, axis=-1).reshape(sq, r)
                      for pair in ((cos, cos), (-sin, sin))]
        rot = xp.concatenate(spread, axis=-1).astype(np.float32)
    return rot, (int(start), r, bool(halves))


def _call_flash(q, k, v, q_offset, kv_offset, sm_scale, causal, block_q,
                block_k, interpret, n_heads, rope=0, n_kv_heads=0,
                window=None, q_rotary=None, keep=None, q_norm=None,
                return_q=False):
    """``_flash`` on a public entry's arguments: ``(out, lse)``, and with
    ``return_q`` the q the scores saw, a constant."""
    # Offsets given as Python ints (the model path: 0, 0) are also kept
    # static, for the build-time tile counters; the kernels read the
    # traced scalars either way.
    static_offsets = None
    if all(isinstance(x, (int, np.integer)) for x in (q_offset, kv_offset)):
        static_offsets = (int(q_offset), int(kv_offset))
    window = int(window or 0)
    if window and static_offsets is not None:
        # a window no row reaches the edge of is no window: the call then
        # traces what the causal call traces
        sq = q.shape[1 if n_heads else 2]
        if window >= static_offsets[0] + sq - static_offsets[1]:
            window = 0
    rot = turn = scale = norm = None
    d = q.shape[-1] // (n_heads or 1)
    if q_rotary is not None:
        compiled = not (
            interpret if interpret is not None else _use_interpret()
        )
        rot, turn = _rotary_operand(
            QRotary(*q_rotary), q.shape[1 if n_heads else 2], d, compiled
        )
    if q_norm is not None:
        scale, norm = _norm_operand(QNorm(*q_norm), d)
    results = _flash(
        q, k, v, jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(kv_offset, jnp.int32), rot, keep, scale,
        _Static(
            float(sm_scale), bool(causal), int(block_q), int(block_k),
            interpret, int(n_heads), static_offsets, rope, int(n_kv_heads),
            window, turn, norm, bool(return_q),
        ),
    )
    if return_q:
        with jax.named_scope(_GLUE_SCOPE):
            return (*results[:2], lax.stop_gradient(results[2]))
    return results


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
    n_kv_heads: int = 0,
    window: Optional[int] = None,
    q_rotary: Optional[QRotary] = None,
    keep=None,
    q_norm: Optional[QNorm] = None,
    return_q: bool = False,
) -> Tuple[jax.Array, ...]:
    """Blockwise attention returning ``(out, lse)``.

    ``layout="bshd"`` (default): q ``[B, Sq, H, D]``, k/v
    ``[B, Skv, H, D]``.  ``layout="bhsd"``: head-major ``[B, H, S, D]``
    — heads on a leading block dim.  ``layout="bsm"``: packed
    ``[B, S, H*D]`` with ``n_heads`` given — the projection's native
    layout; heads are sliced from the minor axis inside the kernel, so
    q/k/v/out need no relayout at all (the r4 ``bhsd`` path still paid
    the head transpose by folding it into the projection dots, which
    then ran at ~43%% of MXU peak: measured before PR 1, the record went
    in PR 30).
    v (and so ``out``) may have another head width than q and k in every
    layout (latent attention: q / k heads 192 wide, v / out heads 128);
    the scores scale by the q / k width unless ``sm_scale`` is given.
    ``lse`` is fp32 ``[B, H, Sq]`` in every layout — the log-sum-exp of
    each row's (masked) scores, the residual needed to merge partial
    attention across K/V shards (:func:`combine_blocks`) and to run the
    exact backward.  ``q_offset``/``kv_offset`` are the global positions
    of row 0 (may be traced), used only for causal masking.

    Query groups: k and v may hold fewer heads than q, ``H % H_kv == 0``;
    query head ``n`` then reads K/V head ``n // (H / H_kv)``.  In the
    head-major layouts K and V's own head axis says so; in ``"bsm"`` pass
    ``n_kv_heads`` (k / v are ``[B, Skv, H_kv * D]``).  A program's K/V
    block is ONE K/V head's, read once for the query heads of the program,
    and dK / dV add up over the heads that share it in the kernel's float32
    accumulator: no K, V, dK or dV of ``H`` heads exists in HBM.

    ``window`` (needs ``causal=True``): row ``i`` sees column ``j`` only
    where ``0 <= i - j < window`` in global positions.  Tiles and blocks
    wholly outside the band are neither visited nor copied nor given a grid
    step, in all three passes; a window that no row reaches the edge of
    (``window >= q_offset + Sq - kv_offset``, static offsets) traces the
    causal call.  The windowed kernels' names end in ``_window``.

    ``q_rotary`` (:class:`QRotary`): q is passed unrotated and rotated in
    the kernels; k is passed rotated.

    ``q_norm`` (:class:`QNorm`): q is passed as its projection leaves it and
    each head is RMS-normed in the kernels, ahead of the rotation; k is
    passed normed.  ``return_q``: ``(out, lse, q_seen)``, the q the scores
    saw (normed, rotated, as the kernels rounded it; q itself without
    either) in q's layout and under ``stop_gradient``: what a second reader
    of the attention's own operand takes (``ops/dsa_kernels.dsa_index_loss``)
    where XLA would norm and rotate q once more.

    ``keep`` (needs ``causal=True``): int8 ``[B, Skv, Sq]``, keys by queries
    as the kernels hold their scores; row ``i`` of every head sees column
    ``j`` only where the causal geometry (and ``window``) lets it AND
    ``keep[b, j, i] != 0``.  A constant of the call (no cotangent).  The
    grids and the tile classes stay the geometry's: the kernels read one
    more ``[block_k, block_q]`` block a pair and skip nothing for it, so a
    mask is for kept sets that are scattered (``ops/dsa_kernels.py``
    builds one).  A row that keeps nothing gives zeros and ``lse = -inf``.
    The kernels' names end in ``_select``.
    """
    packed = layout == "bsm"
    if packed and n_heads <= 0:
        raise ValueError("layout='bsm' requires n_heads")
    if layout not in ("bshd", "bhsd", "bsm"):
        raise ValueError(
            f"layout must be 'bshd', 'bhsd' or 'bsm', got {layout!r}"
        )
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} needs causal=True and window >= 1 (got "
            f"causal={causal}; q {q.shape}, k {k.shape})"
        )
    head_axis = 2 if layout == "bshd" else 1
    h = n_heads if packed else q.shape[head_axis]
    h_kv = (n_kv_heads or n_heads) if packed else k.shape[head_axis]
    if not packed and n_kv_heads and n_kv_heads != h_kv:
        raise ValueError(
            f"n_kv_heads={n_kv_heads} is for layout='bsm'; in "
            f"layout={layout!r} k {k.shape} carries its own head axis "
            f"({h_kv} heads)"
        )
    if h % h_kv or (packed and (k.shape[-1] % h_kv or v.shape[-1] % h_kv)):
        raise ValueError(
            f"{h} query heads (q {q.shape}) do not share {h_kv} K/V heads "
            f"(k {k.shape}, v {v.shape}) evenly: n_heads % n_kv_heads != 0"
        )
    compiled = not (interpret if interpret is not None else _use_interpret())
    if packed and compiled and any(
        (x.shape[-1] // heads) % 64 != 0
        for x, heads in ((q, h), (v, h_kv))
    ):
        raise ValueError(
            "layout='bsm' needs head_dim % 64 == 0 on TPU (Mosaic lane "
            f"slicing is 64-aligned); got head_dim="
            f"{q.shape[-1] // h} (v: {v.shape[-1] // h_kv}) — use "
            "layout='bhsd'"
        )
    if packed and compiled and 1 < h_kv < h and any(
        (x.shape[-1] // h_kv) % _LANES for x in (k, v)
    ):
        raise ValueError(
            "layout='bsm' with query groups needs K/V heads that are "
            f"multiples of {_LANES} wide on TPU (one head is a block's lane "
            f"axis); got k {k.shape}, v {v.shape} for {h_kv} heads — use "
            "layout='bhsd'"
        )
    if keep is not None:
        seq_axis = 1 if layout in ("bshd", "bsm") else 2
        want = (q.shape[0], k.shape[seq_axis], q.shape[seq_axis])
        if not causal or keep.shape != want or keep.dtype != jnp.int8:
            raise ValueError(
                f"keep needs causal=True and an int8 [B, Skv, Sq] = {want} "
                f"mask (got causal={causal}, {keep.dtype} {keep.shape})"
            )
    if sm_scale is None:
        d = q.shape[-1] // n_heads if packed else q.shape[-1]
        sm_scale = 1.0 / float(np.sqrt(d))
    if layout == "bshd":
        with jax.named_scope(_GLUE_SCOPE):
            q = jnp.moveaxis(q, 2, 1)
            k = jnp.moveaxis(k, 2, 1)
            v = jnp.moveaxis(v, 2, 1)
    out, lse, *q_seen = _call_flash(
        q, k, v, q_offset, kv_offset, sm_scale, causal, block_q, block_k,
        interpret, n_heads if packed else 0,
        n_kv_heads=h_kv if packed and h_kv != h else 0, window=window,
        q_rotary=q_rotary, keep=keep, q_norm=q_norm, return_q=return_q,
    )
    if layout == "bshd":
        with jax.named_scope(_GLUE_SCOPE):
            out = jnp.moveaxis(out, 1, 2)
            q_seen = [jnp.moveaxis(x, 1, 2) for x in q_seen]
    return (out, lse, *q_seen)


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
    n_kv_heads: int = 0,
    window: Optional[int] = None,
    q_rotary: Optional[QRotary] = None,
    keep=None,
    q_norm: Optional[QNorm] = None,
) -> jax.Array:
    """Drop-in memory-efficient replacement for
    ``models.transformer.dot_product_attention`` (same signature shape);
    ``n_kv_heads``, ``window``, ``q_rotary``, ``keep`` and ``q_norm`` as
    :func:`flash_attention_with_lse`.

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
        n_kv_heads=n_kv_heads,
        window=window,
        q_rotary=q_rotary,
        keep=keep,
        q_norm=q_norm,
    )
    return out


def flash_attention_latent(
    q,
    kv,
    k_rope,
    *,
    n_heads: int,
    causal: bool = False,
    q_offset=0,
    kv_offset=0,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    q_rotary: Optional[QRotary] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Blockwise attention over keys and values as latent attention holds
    them, returning ``(out, lse)`` like :func:`flash_attention_with_lse`.

    With ``H`` heads whose keys are ``n`` unshared columns and ``r`` rotary
    columns that every head of a position shares, and values ``dv`` wide:
    q ``[B, Sq, H*(n+r)]``, ``[q_nope | q_rope]`` a head (rotary applied,
    or with ``q_rotary`` (:class:`QRotary`, ``start=n``) the ``q_b``
    projection's output as it is: the kernels rotate it and hand back the
    gradient of that output);
    kv ``[B, Skv, H*(n+dv)]``, ``[k_nope | v]`` a head, which is the
    ``kv_b`` projection's output as the matmul leaves it; k_rope
    ``[B, Skv, r]`` (rotary applied).  out ``[B, Sq, H*dv]``, lse fp32
    ``[B, H, Sq]``.  The widths are read off the shapes.

    The same three kernels as the ``q, k, v`` entry run, with the same
    plan; their K/V block is one block of ``kv`` and one of ``k_rope``, a
    head's ``[rows, n+r]`` key tile is put together in VMEM (``_k_head``),
    and neither ``[.., H, n+r]`` keys nor a separate v exist in HBM.  The
    backward returns cotangents in the operands' own layouts: ``[dk_nope |
    dv]`` a head, and ``k_rope``'s summed over the heads in float32.
    Scores scale by ``1/sqrt(n+r)`` unless ``sm_scale`` is given.
    Compiled, ``n``, ``r`` and ``dv`` have to be multiples of 64 (lane
    slicing).
    """
    r = k_rope.shape[-1]
    n = q.shape[-1] // n_heads - r
    dv = kv.shape[-1] // n_heads - n
    if (q.shape[-1] % n_heads or kv.shape[-1] % n_heads
            or min(n, dv) <= 0 or kv.shape[:2] != k_rope.shape[:2]):
        raise ValueError(
            f"q {q.shape}, kv {kv.shape}, k_rope {k_rope.shape} are not "
            f"[B,Sq,H*(n+r)], [B,Skv,H*(n+dv)], [B,Skv,r] for H={n_heads}"
        )
    if any(w % 64 for w in (n, r, dv)) and not (
        interpret if interpret is not None else _use_interpret()
    ):
        raise ValueError(
            "flash_attention_latent needs widths that are multiples of 64 "
            f"on TPU (Mosaic lane slicing); got n={n}, r={r}, dv={dv}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(n + r))
    with jax.named_scope(_GLUE_SCOPE):
        k_rope = k_rope.astype(kv.dtype)
    return _call_flash(
        q, kv, k_rope, q_offset, kv_offset, sm_scale, causal, block_q,
        block_k, interpret, n_heads, rope=r, q_rotary=q_rotary,
    )


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
