"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

NEW capability relative to the reference (SURVEY.md §5.7: absent there; its
``alltoall`` — ``operations.cc:1101-1162`` — is exactly the primitive
Ulysses needs, and its Adasum p2p — ``ops/adasum/adasum.h:55-61`` — is the
neighbor-exchange ring attention needs). Long context is first-class here:

* **Ring attention**: the sequence is sharded over the ``sp`` mesh axis;
  each device keeps its Q block resident while K/V blocks rotate around
  the ICI ring via ``lax.ppermute``, accumulating attention with an
  online-softmax (flash-style) update. Memory per device is O(S/n); the
  ring rides nearest-neighbor ICI links — the layout the TPU torus is
  built for.
* **Ulysses**: ``all_to_all`` swaps the sharded axis from sequence to
  heads, runs dense attention on full sequence with H/n heads, and swaps
  back. Cheaper at moderate S, but caps parallelism at the head count.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax



def _online_update(o, m, l, scores, v, scale):
    """One flash-attention accumulation step.

    o: [B,S,H,D] running numerator; m/l: [B,H,S] running max / denominator;
    scores: [B,H,S,Skv] fp32; v: [B,Skv,H,D].
    """
    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)  # [B,H,S]
    p = jnp.exp(scores - m_new[..., None])  # [B,H,S,Skv]
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(q, k, v, *, axis: str, causal: bool = False,
                   use_flash: bool = False, block_q: int = 512,
                   block_k: int = 512):
    """Exact attention over a sequence sharded along mesh axis ``axis``.

    Args: q/k/v ``[batch, seq_shard, heads, head_dim]`` (this device's
    sequence block; block r holds global positions ``r*S .. (r+1)*S-1``).
    Returns the attention output in the same layout. Differentiable
    (``ppermute`` has a transpose rule), so it drops into training steps.

    ``use_flash=True`` computes each ring hop with the Pallas blockwise
    kernel (:mod:`horovod_tpu.ops.pallas_kernels`): per-hop partials
    ``(out, lse)`` are merged by exact log-sum-exp combination, so the
    S_shard × S_shard score matrix never hits HBM either.
    """
    if use_flash:
        return _ring_attention_flash(
            q, k, v, axis=axis, causal=causal, block_q=block_q,
            block_k=block_k,
        )
    n = int(lax.axis_size(axis))
    r = lax.axis_index(axis)
    b, s, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    q32 = q.astype(jnp.float32)

    o = jnp.zeros((b, s, h, d), jnp.float32)
    m = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, s), jnp.float32)

    q_pos = r * s + jnp.arange(s)  # global positions of this Q block

    kv = (k, v)
    for step in range(n):
        k_blk, v_blk = kv
        kv_rank = (r - step) % n
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
        ) * scale
        if causal:
            kv_pos = kv_rank * s + jnp.arange(s)
            cmask = q_pos[:, None] >= kv_pos[None, :]  # [S, Skv]
            scores = jnp.where(cmask[None, None], scores, -jnp.inf)
        o, m, l = _online_update(o, m, l, scores, v_blk, scale)
        if step != n - 1:
            # Rotate K/V one hop around the ring (nearest-neighbor ICI).
            perm = [(i, (i + 1) % n) for i in range(n)]
            kv = jax.tree.map(lambda x: lax.ppermute(x, axis, perm), kv)

    # Fully-masked rows (can happen only with causal & empty blocks) have
    # l == 0; guard the division.
    l_safe = jnp.where(l > 0, l, 1.0)
    out = o / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_attention_flash(q, k, v, *, axis: str, causal: bool,
                          block_q: int, block_k: int):
    """Ring attention with the Pallas flash kernel as the per-hop block."""
    from ..ops.pallas_kernels import combine_blocks, flash_attention_with_lse

    n = int(lax.axis_size(axis))
    r = lax.axis_index(axis)
    b, s, h, d = q.shape
    # Lane-aligned head dims ride the packed kernel layout: [B,S,H,D] ↔
    # [B,S,H·D] are FREE reshapes (adjacent minor dims), so every ring
    # hop runs with zero relayout — the bshd path instead pays a
    # [B,S,H,D]→[B,H,S,D] transpose per hop.
    packed = d % 64 == 0

    o = jnp.zeros((b, s, h, d), jnp.float32)
    lse = jnp.full((b, h, s), -jnp.inf, jnp.float32)

    kv = (k, v)
    for step in range(n):
        k_blk, v_blk = kv
        kv_rank = (r - step) % n
        if packed:
            o_i, lse_i = flash_attention_with_lse(
                q.reshape(b, s, h * d),
                k_blk.reshape(b, s, h * d),
                v_blk.reshape(b, s, h * d),
                causal=causal,
                q_offset=r * s,
                kv_offset=kv_rank * s,
                block_q=block_q,
                block_k=block_k,
                layout="bsm",
                n_heads=h,
            )
            o_i = o_i.reshape(b, s, h, d)
        else:
            o_i, lse_i = flash_attention_with_lse(
                q,
                k_blk,
                v_blk,
                causal=causal,
                q_offset=r * s,
                kv_offset=kv_rank * s,
                block_q=block_q,
                block_k=block_k,
            )
        o, lse = combine_blocks(o, lse, o_i.astype(jnp.float32), lse_i)
        if step != n - 1:
            perm = [(i, (i + 1) % n) for i in range(n)]
            kv = jax.tree.map(lambda x: lax.ppermute(x, axis, perm), kv)
    return o.astype(q.dtype)


def ulysses_attention(q, k, v, *, axis: str, causal: bool = False,
                      attention_fn=None):
    """Ulysses-style SP: all_to_all seq→heads, dense attention, heads→seq.

    q/k/v ``[batch, seq_shard, heads, head_dim]``; ``heads`` must be
    divisible by the axis size. Built on the same primitive as the
    reference's ``hvd.alltoall``.
    """
    n = int(lax.axis_size(axis))
    b, s, h, d = q.shape
    if h % n:
        raise ValueError(f"heads {h} not divisible by sp axis size {n}")

    def seq_to_heads(x):
        # [B, S/n, H, D] --all_to_all--> [B, S, H/n, D]
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if attention_fn is None:
        from ..models.transformer import dot_product_attention

        attention_fn = dot_product_attention
    out = attention_fn(qf, kf, vf, causal=causal)
    return heads_to_seq(out)
