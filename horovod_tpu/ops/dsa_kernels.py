"""Learned sparse attention's "select" family: a lightning indexer scores
every earlier position, each query keeps its ``top_k`` best, and the indexer
learns from the attention it prunes (DeepSeek-V3.2-Exp's sparse attention,
the sparse-training stage).

Three pieces, each a Pallas kernel on the TPU and the same equations in XLA
elsewhere (``use_kernel=None`` decides by ``context.device_platform``, as
``kda_kernels.kda_attention`` does):

* :func:`dsa_select` (kernel ``hvd_dsa_select``): from the indexer's
  queries ``q_idx [B, S, H_I * d_I]``, its ONE key head ``k_idx [B, S,
  d_I]`` and the head weights ``w [B, S, H_I]`` (float32, already scaled)::

      I[t, s]  = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])       s <= t
      tau_t    = the top_k-th largest of I[t, 0..t]   (-inf under top_k entries)
      keep     = { (t, s) : s <= t, I[t, s] >= tau_t }            ties all kept
      lse_I[t] = log sum_{s kept} exp(I[t, s])

  ``keep`` leaves as int8 ``[B, S, S]`` KEYS BY QUERIES (``keep[b, s, t]``),
  the layout in which the flash kernels hold their scores, so
  ``pallas_kernels.flash_attention_with_lse(..., keep=keep)`` reads a
  ``[block_k, block_q]`` block of it as it lies. A program takes ``block_q``
  queries: their scores against every key tile up to the diagonal go into a
  VMEM block ``[S, block_q]`` as the order-preserving integer image of
  float32 (``_ordered``), ``tau`` is found EXACTLY by bisection over that
  image (32 rounds of compare-and-count, one bit a round), then the mask is
  written and ``lse_I`` summed. ``I`` never reaches HBM. The products ``q_idx
  . k_idx`` take the operands in their storage dtype (bf16 from the
  projections) and accumulate in float32; ReLU, the weights, the sum over
  heads, ``tau``, the comparison and ``lse_I`` are float32.
* the masked attention itself is ``pallas_kernels``' three flash bodies
  under ``keep=`` (kernels ``hvd_flash_*_select``).
* :func:`dsa_index_loss` (kernel ``hvd_dsa_kl``): with ``pbar[t, s] = mean_n
  p_n[t, s]`` the heads' mean attention over the kept set (recomputed from
  q, k and the forward's per-head ``lse``; a constant: no gradient reaches
  q, k or ``lse``)::

      L_I = mean_{b, t} sum_{s kept} pbar (log pbar - (I[t, s] - lse_I[t]))
      dL_I / dI[t, s] = (exp(I[t, s] - lse_I[t]) - pbar[t, s]) / (B S)

  (``sum_s pbar = 1``: each ``p_n`` is normalised over the kept set by its
  own ``lse``). Forward value and the three gradients in ONE pass over the
  (q tile, k tile) pairs under the diagonal: a pair recomputes the 32
  heads' ``p_n`` and the tile of ``I``, adds its terms of the loss, and
  sends ``dI`` back through the ReLU to ``dq_idx`` and ``dw`` (accumulated
  over a q tile's k tiles) and to ``dk_idx``, whose whole ``[S, d_I]``
  float32 array is one output block that stays in VMEM through a
  sequence's grid steps (one key head: 2 MB at 8,192). A ``custom_vjp``
  whose forward returns ``L_I`` and saves the three gradients.

All score-sized arrays are held keys-by-queries, ``[cols, rows]``, like the
flash kernels': what is kept a query (``w``, ``lse``, ``tau``, ``lse_I``,
the loss's terms) is a ``[1, rows]`` vector along the lanes.

Build-time counters (always on, booked where a select KERNEL call is built,
as ``kda_kernels._book`` does, so the XLA form that draws a model's
parameters on a few positions counts nothing; from ``S`` and ``top_k``
alone): ``dsa.calls``, ``dsa.entries.causal`` (``B S (S
+ 1) / 2``), ``dsa.entries.kept`` (``B sum_t min(t + 1, top_k)``: ties
beyond ``top_k`` are not in it) and ``dsa.mask_bytes`` (``B S^2``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..context import device_platform
from ..obs import registry as _registry

__all__ = ["dsa_select", "dsa_index_loss", "index_scores", "kept_entries"]

_VMEM = pltpu.VMEM
# ``jax.named_scope`` of the entries' own XLA operations around the kernels
# (padding, the weights' transpose, the slices back, the loss's last sum)
# and of the whole XLA form: the models' ``index_proj`` (docs/api.md). The
# ``pallas_call``s themselves stay outside it, under their ``name=`` only.
_GLUE_SCOPE = "index_proj"
_INT_MIN = np.int32(-(2 ** 31))
_LOW31 = np.int32(2 ** 31 - 1)


def kept_entries(seq_len: int, top_k: int) -> int:
    """Entries one sequence keeps: row ``t`` its ``min(t + 1, top_k)``."""
    full = min(seq_len, top_k)
    return full * (full + 1) // 2 + (seq_len - full) * top_k


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _ordered(x):
    """float32 -> int32 whose signed order is the floats' (``-0.0`` just
    under ``+0.0``; the scores here are sums that start from ``+0.0`` and
    hold no ``-0.0``). Its own inverse on the integer side: ``_floats``."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ _LOW31)


def _floats(key):
    return lax.bitcast_convert_type(
        jnp.where(key >= 0, key, key ^ _LOW31), jnp.float32
    )


def _nt(a, b):
    """``a [m, c] . b [n, c] -> [m, n]`` float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def index_scores(q_idx, k_idx, w):
    """``I`` whole, float32 ``[B, S, S]`` queries by keys, no mask: the
    definition the kernels are tested against (XLA, for small ``S``)."""
    b, s, _ = k_idx.shape
    h = w.shape[-1]
    dots = jnp.einsum(
        "bthd,bsd->bhts", q_idx.reshape(b, s, h, -1), k_idx,
        preferred_element_type=jnp.float32,
    )
    return jnp.einsum("bth,bhts->bts", w.astype(jnp.float32),
                      jax.nn.relu(dots))


def _select_xla(q_idx, k_idx, w, top_k: int):
    b, s, _ = k_idx.shape
    scores = index_scores(q_idx, k_idx, w)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    # the k-th largest of a row (-inf where the row holds fewer: all kept)
    tau = lax.top_k(scores, min(top_k, s))[0][..., -1]
    if top_k > s:
        tau = jnp.full_like(tau, -jnp.inf)
    keep = jnp.logical_and(causal, scores >= tau[..., None])
    lse = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return keep.swapaxes(1, 2).astype(jnp.int8), tau, lse


class _Plan(NamedTuple):
    b: int
    s: int
    s_pad: int
    block_q: int
    block_k: int
    h_idx: int
    d_idx: int
    top_k: int
    interpret: bool


def _plan(k_idx, w, *, top_k, block_q, block_k, interpret) -> _Plan:
    b, s, d_idx = k_idx.shape
    if interpret is None:
        interpret = device_platform() != "tpu"
    block_q = min(block_q, _round_up(s, 8))
    block_k = min(block_k, _round_up(s, 8))
    s_pad = _round_up(s, max(block_q, block_k))
    if s_pad % block_q or s_pad % block_k:
        raise ValueError(f"blocks {block_q} x {block_k} do not tile {s_pad}")
    return _Plan(b, s, s_pad, block_q, block_k, w.shape[-1], d_idx,
                 int(top_k), bool(interpret))


def _pad_rows(x, p: _Plan):
    if p.s_pad == p.s:
        return x
    # zeros: a padded key lies after every real query and is never kept;
    # a padded query's rows are cut off again
    return jnp.pad(x, ((0, 0), (0, p.s_pad - p.s), (0, 0)))


def _params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=64 << 20,
    )


def _index_tile(k_tile, q_ref, wt_ref, p: _Plan):
    """One ``[block_k, block_q]`` tile of ``I``: ``k_tile [block_k, d_I]``
    against the program's query block, head by head."""
    total = jnp.zeros((k_tile.shape[0], q_ref.shape[1]), jnp.float32)
    for j in range(p.h_idx):
        dots = _nt(k_tile, q_ref[0, :, j * p.d_idx:(j + 1) * p.d_idx])
        total = total + wt_ref[0, j:j + 1, :] * jnp.maximum(dots, 0.0)
    return total


def _select_kernel(q_ref, k_ref, wt_ref, keep_ref, tau_ref, lse_ref, key_ref,
                   *, p: _Plan):
    """grid ``(b, q block)``. q_ref ``[1, block_q, H_I d_I]``; k_ref ``[1,
    S_pad, d_I]`` (whole); wt_ref ``[1, H_I, block_q]``; keep_ref ``[1,
    S_pad, block_q]`` int8; tau_ref / lse_ref ``[1, 8, block_q]``; key_ref:
    VMEM ``[S_pad, block_q]`` int32, the block's scores as ``_ordered``
    keys, ``INT_MIN`` (under every float's key) beyond the diagonal."""
    bq, bk = p.block_q, p.block_k
    row0 = pl.program_id(1) * bq
    n_tiles = (row0 + bq + bk - 1) // bk  # key tiles with a causal entry
    q_pos = row0 + lax.broadcasted_iota(jnp.int32, (1, bq), 1)

    def rows(j):
        return pl.ds(pl.multiple_of(j * bk, bk), bk)

    def score(j, top):
        tile = _index_tile(k_ref[0, rows(j), :], q_ref, wt_ref, p)
        col = j * bk + lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        causal = col <= q_pos
        key_ref[rows(j), :] = jnp.where(causal, _ordered(tile), _INT_MIN)
        return jnp.maximum(top, jnp.max(
            jnp.where(causal, tile, -jnp.inf), axis=0, keepdims=True
        ))

    top = lax.fori_loop(0, n_tiles, score,
                        jnp.full((1, bq), -jnp.inf, jnp.float32))

    # tau's key, bit by bit from the top, in the unsigned order (a key XOR
    # INT_MIN): the largest value that at least top_k keys reach. A row
    # with fewer causal entries ends at 0, which every key reaches.
    def settle(r, found):
        trial = found | jnp.left_shift(jnp.int32(1), 31 - r)
        level = trial ^ _INT_MIN

        def count(j, n):
            return n + jnp.sum(
                (key_ref[rows(j), :] >= level).astype(jnp.int32), axis=0,
                keepdims=True,
            )

        reached = lax.fori_loop(0, n_tiles, count,
                                jnp.zeros((1, bq), jnp.int32))
        return jnp.where(reached >= p.top_k, trial, found)

    found = lax.fori_loop(0, 32, settle, jnp.zeros((1, bq), jnp.int32))
    # at least INT_MIN + 1, so that nothing beyond the diagonal is kept
    level = jnp.maximum(found ^ _INT_MIN, _INT_MIN + 1)

    def write(j, total):
        key = key_ref[rows(j), :]
        kept = key >= level
        keep_ref[0, rows(j), :] = jnp.where(kept, 1, 0).astype(jnp.int8)
        return total + jnp.sum(
            jnp.where(kept, jnp.exp(_floats(key) - top), 0.0), axis=0,
            keepdims=True,
        )

    total = lax.fori_loop(0, n_tiles, write,
                          jnp.zeros((1, bq), jnp.float32))

    def blank(j, carry):
        keep_ref[0, rows(j), :] = jnp.zeros((bk, bq), jnp.int8)
        return carry

    lax.fori_loop(n_tiles, p.s_pad // bk, blank, 0)
    tau = jnp.where(found == 0, -jnp.inf, _floats(level))
    tau_ref[0] = jnp.broadcast_to(tau, (8, bq))
    lse_ref[0] = jnp.broadcast_to(top + jnp.log(total), (8, bq))


@functools.partial(jax.jit, static_argnames=("p",), inline=True)
def _select_call(q_idx, k_idx, w, *, p: _Plan):
    with jax.named_scope(_GLUE_SCOPE):
        q_idx, k_idx = _pad_rows(q_idx, p), _pad_rows(k_idx, p)
        wt = _pad_rows(w.astype(jnp.float32), p).swapaxes(1, 2)
    bq = p.block_q
    row = jax.ShapeDtypeStruct((p.b, 8, p.s_pad), jnp.float32)
    keep, tau, lse = pl.pallas_call(
        functools.partial(_select_kernel, p=p),
        grid=(p.b, p.s_pad // bq),
        in_specs=[
            pl.BlockSpec((1, bq, q_idx.shape[-1]), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, p.s_pad, p.d_idx), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, p.h_idx, bq), lambda b, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, p.s_pad, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 8, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 8, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p.b, p.s_pad, p.s_pad), jnp.int8), row, row,
        ],
        scratch_shapes=[_VMEM((p.s_pad, bq), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        interpret=p.interpret,
        name="hvd_dsa_select",
    )(q_idx, k_idx, wt)
    with jax.named_scope(_GLUE_SCOPE):
        return (keep[:, :p.s, :p.s], tau[:, 0, :p.s], lse[:, 0, :p.s])


def _book(p: _Plan) -> None:
    reg = _registry.always()
    reg.counter("dsa.calls").inc()
    reg.counter("dsa.entries.causal").inc(p.b * p.s * (p.s + 1) // 2)
    reg.counter("dsa.entries.kept").inc(p.b * kept_entries(p.s, p.top_k))
    reg.counter("dsa.mask_bytes").inc(p.b * p.s * p.s)


def dsa_select(q_idx, k_idx, w, *, top_k: int,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None,
               block_q: int = 128, block_k: int = 512):
    """``(keep, tau, lse_I)`` of the module's first equations: ``keep`` int8
    ``[B, S, S]`` keys by queries, ``tau`` and ``lse_I`` float32 ``[B, S]``.
    Constants of the step: nothing here is differentiated (the indexer
    learns through :func:`dsa_index_loss`), so the operands are taken
    under ``stop_gradient``.

    ``use_kernel``: None takes the Pallas kernel where the world's devices
    are TPUs and XLA (``I`` whole, ``lax.top_k``'s k-th value) elsewhere;
    True runs the kernel anywhere (interpreted off the TPU). ``block_q`` /
    ``block_k``: the queries a program takes and the key tile it scores
    them against (a model's ``q_chunk_size`` / ``kv_chunk_size`` may go
    here; they change no result)."""
    if use_kernel is None:
        use_kernel = device_platform() == "tpu"
    with jax.named_scope(_GLUE_SCOPE):
        q_idx, k_idx, w = (lax.stop_gradient(x) for x in (q_idx, k_idx, w))
        if not use_kernel:
            return _select_xla(q_idx, k_idx, w, top_k)
        k_idx = k_idx.astype(q_idx.dtype)
    p = _plan(k_idx, w, top_k=top_k, block_q=block_q, block_k=block_k,
              interpret=interpret)
    _book(p)
    return _select_call(q_idx, k_idx, w, p=p)


# ---------------------------------------------------------------------------
# The index loss
# ---------------------------------------------------------------------------


def _index_loss_xla(q, k, q_idx, k_idx, w, keep, *, n_heads: int,
                    n_kv_heads: int, sm_scale: float):
    """The loss written out whole (small ``S``): differentiable in
    ``q_idx``, ``k_idx`` and ``w`` by XLA's own rules. Each head's ``p_n``
    is the softmax over the kept set, so no ``lse`` is handed in."""
    b, s, _ = k_idx.shape
    d = q.shape[-1] // n_heads
    q, k = lax.stop_gradient(q), lax.stop_gradient(k)
    kept = keep.swapaxes(1, 2) != 0  # queries by keys
    heads = jnp.repeat(k.reshape(b, s, n_kv_heads, d), n_heads // n_kv_heads,
                       axis=2)
    dots = jnp.einsum("bthd,bshd->bhts", q.reshape(b, s, n_heads, d), heads,
                      preferred_element_type=jnp.float32) * sm_scale
    pbar = jax.nn.softmax(
        jnp.where(kept[:, None], dots, -jnp.inf), axis=-1
    ).mean(axis=1)
    scores = jnp.where(kept, index_scores(q_idx, k_idx, w), -jnp.inf)
    logq = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    positive = pbar > 0.0
    terms = jnp.where(
        positive,
        pbar * (jnp.log(jnp.where(positive, pbar, 1.0))
                - jnp.where(kept, logq, 0.0)),
        0.0,
    )
    return terms.sum() / (b * s)


def _kl_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, wt_ref, keep_ref,
               lsei_ref, loss_ref, dqi_ref, dwt_ref, dki_ref, dqi_acc,
               dwt_acc, loss_acc, *, p: _Plan, n_heads: int, n_kv_heads: int,
               sm_scale: float, scale: float):
    """grid ``(b, q block, k block)``, the k blocks innermost; every step
    of a sequence runs in turn (``dki_ref`` is the whole ``[1, S_pad, d_I]``
    float32 gradient, one block that the sequence's steps add into). q_ref
    ``[1, block_q, H d]``; k_ref ``[1, block_k, H_kv d]``; lse_ref ``[1, H,
    block_q]``; qi_ref / ki_ref / wt_ref: as the select kernel's, ki_ref a
    tile; keep_ref ``[1, block_k, block_q]``; lsei_ref ``[1, 1,
    block_q]``; loss_ref ``[1, 8, block_q]`` (a query's terms, unscaled);
    dqi_ref ``[1, block_q, H_I d_I]``; dwt_ref ``[1, H_I, block_q]``."""
    bq, bk = p.block_q, p.block_k
    i, j = pl.program_id(1), pl.program_id(2)
    last = ((i + 1) * bq - 1) // bk  # the diagonal's k block
    d = q_ref.shape[2] // n_heads
    ratio = n_heads // n_kv_heads

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(j == 0)
    def _():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dwt_acc[...] = jnp.zeros_like(dwt_acc)
        loss_acc[...] = jnp.zeros_like(loss_acc)

    @pl.when(j <= last)
    def _():
        kept = keep_ref[0].astype(jnp.int32) != 0  # [bk, bq]
        mean = jnp.zeros((bk, bq), jnp.float32)
        for n in range(n_heads):
            lo = (n // ratio) * d
            dots = _nt(k_ref[0, :, lo:lo + d], q_ref[0, :, n * d:(n + 1) * d])
            # an entry that is not kept may overflow: it is masked below
            mean = mean + jnp.exp(dots * sm_scale - lse_ref[0, n:n + 1, :])
        mean = jnp.where(kept, mean * (1.0 / n_heads), 0.0)
        k_tile = ki_ref[0]
        scores = _index_tile(k_tile, qi_ref, wt_ref, p)
        logq = jnp.where(kept, scores - lsei_ref[0], 0.0)
        positive = mean > 0.0
        loss_acc[...] += jnp.sum(
            jnp.where(
                positive,
                mean * (jnp.log(jnp.where(positive, mean, 1.0)) - logq), 0.0,
            ), axis=0, keepdims=True,
        )
        d_scores = (jnp.where(kept, jnp.exp(jnp.minimum(logq, 0.0)), 0.0)
                    - mean) * scale
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        d_key = jnp.zeros((bk, p.d_idx), jnp.float32)
        for h in range(p.h_idx):
            cols = slice(h * p.d_idx, (h + 1) * p.d_idx)
            q_head = qi_ref[0, :, cols]
            dots = _nt(k_tile, q_head)
            weight = wt_ref[0, h:h + 1, :]
            dwt_acc[h:h + 1, :] += jnp.sum(
                d_scores * jnp.maximum(dots, 0.0), axis=0, keepdims=True
            )
            d_dots = jnp.where(dots > 0.0, d_scores * weight, 0.0).astype(
                k_tile.dtype
            )
            dqi_acc[:, cols] += lax.dot_general(
                d_dots, k_tile, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            d_key = d_key + jnp.dot(d_dots, q_head,
                                    preferred_element_type=jnp.float32)
        dki_ref[0, rows, :] += d_key

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        loss_ref[0] = jnp.broadcast_to(loss_acc[...], (8, bq))
        dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
        dwt_ref[0] = dwt_acc[...]


@functools.partial(
    jax.jit, static_argnames=("p", "n_heads", "n_kv_heads", "sm_scale"),
    inline=True,
)
def _kl_call(q, k, lse, q_idx, k_idx, w, keep, lse_idx, *, p: _Plan,
             n_heads: int, n_kv_heads: int, sm_scale: float):
    """``(loss, dq_idx, dk_idx, dw)``, the gradients those of the loss."""
    bq, bk, s, s_pad = p.block_q, p.block_k, p.s, p.s_pad
    extra = s_pad - s
    with jax.named_scope(_GLUE_SCOPE):
        q, k, q_idx, k_idx = (_pad_rows(x, p) for x in (q, k, q_idx, k_idx))
        wt = _pad_rows(w.astype(jnp.float32), p).swapaxes(1, 2)
        if extra:  # padded queries keep nothing and carry finite statistics
            lse = jnp.pad(lse, ((0, 0), (0, 0), (0, extra)))
            lse_idx = jnp.pad(lse_idx, ((0, 0), (0, extra)))
            keep = jnp.pad(keep, ((0, 0), (0, extra), (0, extra)))
        lse_idx = lse_idx[:, None, :]

    def k_block(i, j):  # a step beyond the diagonal repeats its block
        return jnp.minimum(j, ((i + 1) * bq - 1) // bk)

    q_side = lambda width: pl.BlockSpec(  # noqa: E731
        (1, bq, width), lambda b, i, j: (b, i, 0)
    )
    k_side = lambda width: pl.BlockSpec(  # noqa: E731
        (1, bk, width), lambda b, i, j: (b, k_block(i, j), 0)
    )
    row = lambda height: pl.BlockSpec(  # noqa: E731
        (1, height, bq), lambda b, i, j: (b, 0, i)
    )
    loss, dq_idx, dwt, dk_idx = pl.pallas_call(
        functools.partial(
            _kl_kernel, p=p, n_heads=n_heads, n_kv_heads=n_kv_heads,
            sm_scale=sm_scale, scale=1.0 / (p.b * s),
        ),
        grid=(p.b, s_pad // bq, s_pad // bk),
        in_specs=[
            q_side(q.shape[-1]), k_side(k.shape[-1]), row(n_heads),
            q_side(q_idx.shape[-1]), k_side(p.d_idx), row(p.h_idx),
            pl.BlockSpec((1, bk, bq),
                         lambda b, i, j: (b, k_block(i, j), i)),
            row(1),
        ],
        out_specs=[
            row(8), q_side(q_idx.shape[-1]), row(p.h_idx),
            pl.BlockSpec((1, s_pad, p.d_idx), lambda b, i, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p.b, 8, s_pad), jnp.float32),
            jax.ShapeDtypeStruct(q_idx.shape, q_idx.dtype),
            jax.ShapeDtypeStruct(wt.shape, jnp.float32),
            jax.ShapeDtypeStruct((p.b, s_pad, p.d_idx), jnp.float32),
        ],
        scratch_shapes=[
            _VMEM((bq, q_idx.shape[-1]), jnp.float32),
            _VMEM((p.h_idx, bq), jnp.float32),
            _VMEM((1, bq), jnp.float32),
        ],
        compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=p.interpret,
        name="hvd_dsa_kl",
    )(q, k, lse, q_idx, k_idx, wt, keep, lse_idx)
    with jax.named_scope(_GLUE_SCOPE):
        return (
            loss[:, 0, :s].sum() / (p.b * s), dq_idx[:, :s],
            dk_idx[:, :s].astype(k_idx.dtype),
            dwt.swapaxes(1, 2)[:, :s].astype(w.dtype),
        )


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _index_loss(*args):
    return _index_loss_fwd(*args)[0]


def _index_loss_fwd(q, k, lse, q_idx, k_idx, w, keep, lse_idx, p, n_heads,
                    n_kv_heads, sm_scale):
    loss, *grads = _kl_call(q, k, lse, q_idx, k_idx, w, keep, lse_idx, p=p,
                            n_heads=n_heads, n_kv_heads=n_kv_heads,
                            sm_scale=sm_scale)
    return loss, (grads, q, k, lse, keep, lse_idx)


def _index_loss_bwd(p, n_heads, n_kv_heads, sm_scale, residuals, g):
    (dq_idx, dk_idx, dw), q, k, lse, keep, lse_idx = residuals
    scaled = lambda x: (x.astype(jnp.float32) * g).astype(x.dtype)  # noqa: E731
    with jax.named_scope(_GLUE_SCOPE):
        return (
            jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
            scaled(dq_idx), scaled(dk_idx), scaled(dw),
            np.zeros(keep.shape, dtype=jax.dtypes.float0),
            jnp.zeros_like(lse_idx),
        )


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def dsa_index_loss(q, k, lse, q_idx, k_idx, w, keep, lse_idx, *,
                   n_heads: int, n_kv_heads: int,
                   sm_scale: Optional[float] = None,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None,
                   block_q: int = 256, block_k: int = 512):
    """``L_I`` (a float32 scalar) of the module's last equations.

    ``q [B, S, H d]`` and ``k [B, S, H_kv d]`` are the attention's own
    operands as its scores take them (normed, rotated), ``lse [B, H, S]``
    its per-head log-sum-exp over the kept set
    (``flash_attention_with_lse(..., keep=keep)``); ``q_idx``, ``k_idx``,
    ``w``, ``keep``, ``lse_idx``: :func:`dsa_select`'s operands and
    results. Differentiable in ``q_idx``, ``k_idx`` and ``w`` only: ``q``,
    ``k`` and ``lse`` are the target's and take zeros (``lse_idx``'s part
    of the gradient is in the closed form, so it must be THESE operands'
    ``lse_I``). ``use_kernel`` / ``interpret``: as :func:`dsa_select`; the
    XLA form takes each head's softmax over the kept set itself and reads
    neither ``lse`` nor ``lse_idx`` (both may be None there). ``block_q``
    / ``block_k``: the tile pair a grid step works (kernels alone at 1 x
    8,192 on the v5e, PERF.md PR 47: 256 x 512 5.39 ms a layer, 256 x 256
    6.43, 128 x 256 8.83)."""
    d = q.shape[-1] // n_heads
    sm_scale = float(sm_scale if sm_scale is not None else d ** -0.5)
    if use_kernel is None:
        use_kernel = device_platform() == "tpu"
    if not use_kernel:
        with jax.named_scope(_GLUE_SCOPE):
            return _index_loss_xla(q, k, q_idx, k_idx, w, keep,
                                   n_heads=n_heads, n_kv_heads=n_kv_heads,
                                   sm_scale=sm_scale)
    p = _plan(k_idx, w, top_k=0, block_q=block_q, block_k=block_k,
              interpret=interpret)
    with jax.named_scope(_GLUE_SCOPE):
        # the target's: constants here, so that their zero cotangents meet
        # no other gradient of theirs
        q, k, lse, lse_idx = (
            lax.stop_gradient(x) for x in (q, k, lse, lse_idx)
        )
        k, lse = k.astype(q.dtype), lse.astype(jnp.float32)
        k_idx, lse_idx = k_idx.astype(q_idx.dtype), lse_idx.astype(jnp.float32)
    return _index_loss(q, k, lse, q_idx, k_idx, w, keep, lse_idx, p,
                       int(n_heads), int(n_kv_heads), sm_scale)
