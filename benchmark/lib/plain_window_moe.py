"""The window / full attention, grouped-head, routed-expert language model
written out in plain ``jax.numpy``: the reference half of ``correct`` for
the ``window_moe_lm`` family.

Float32 throughout, every matmul at ``precision="highest"``, attention as a
masked softmax over whole rows of the score matrix with the mask built from
``valid(i, j)`` as it is written below, K and V repeated to the query heads
by plain indexing, the routed part as a loop over the experts held with a
``[T]`` weight that is zero where the expert was not chosen: no kernel, no
skipped tile, no shared K/V block, no row buffer, no flax, no line of
``horovod_tpu``. It reads the parameter tree the program's modules create
(names below), so both sides start from the same weights.

Equations (no bias anywhere, eps 1e-6). Layer ``l`` with input ``h``, ``H``
query heads and ``H_kv`` K/V heads of width ``d``::

    u = RMSNorm1(h)
    r = u W_r;  p = softmax(r) over all E;  C = the k largest
    w = p[C] / sum(p[C])                       (norm_topk_prob)
    q = u W_q -> H x d;  k = u W_k, v = u W_v -> H_kv x d
    rope_layout[l] = 1:   q, k <- q cos + rotate_half(q) sin, the published
                          convention: column i pairs with column i + d/2
    window_layout[l] = 1: valid(i, j): 0 <= i - j < window
                     = 0: valid(i, j): j <= i
    a = softmax_j(q_i k_j / sqrt(d)) v_j;  query head n reads K/V head
        n // (H / H_kv)
    h' = h + concat(a) W_o
    m = RMSNorm2(h')
    out = h' + sum_{e held} [e in C] w_e W_down,e (relu(W_gate,e m) * W_up,e m)

then a final RMSNorm, an untied head and the mean next-token cross
entropy. The router reads the attention's input ``u``; the experts read
``m``. The terms of experts that are not held are left out, as in the
program (the configuration file's ``deployment``).

Departures from the published description, each the configuration file's
``assumed``: the weights of the chosen experts are written as the published
code has them (softmax over all, renormalised) where the program takes the
softmax over the chosen logits, which is the same number; the rotary
angles are tabulated in float64 and rounded to float32 once (at 16,384
positions a float32 product ``pos x freq`` is off by 1e-3 rad); no
secondary experts (the configuration has no key for them).

So that one sequence of 16,384 fits the reference phase and compiles in
minutes, nothing of which changes a number: the layers run as ONE
``lax.scan`` over their stacked parameters, each a ``jax.checkpoint``, the
two layouts travelling as data (a layer computes its mask from
``windowed`` and keeps or drops the rotation by ``rotated``); attention
runs over blocks of ``q_block`` query rows under ``lax.scan``, each block a
checkpoint, so ``[H, q_block, S]`` scores exist at a time and never ``[H,
S, S]``; the held experts are a ``lax.scan`` over theirs, each term a
checkpoint, and so is the head with its loss.

Parameter tree (``horovod_tpu/models/window_moe.WindowMoELM``):
``embed/embedding [V, D]``, ``head [D, V]``, ``final_norm/scale``,
``block_<i>/{attn_norm, ffn_norm}/scale``, ``block_<i>/attn/{q, k, v,
o}/kernel``, ``block_<i>/{router [D, E], experts_gate [held, D, F],
experts_up, experts_down [held, F, D]}``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int
    window_layout: Tuple[int, ...]  # per layer, indexed modulo the length
    rope_layout: Tuple[int, ...]
    rope_theta: float
    first_expert: int
    top_k: int
    eps: float = 1e-6
    q_block: int = 256  # query rows whose scores are live together
    # The reference is this file in float32. Any other dtype is a CONTROL
    # (``benchmark/controls.py``): the embedding is read in it and every
    # operation follows its operand, so bfloat16 here is the whole model,
    # router, softmax, norms, logits and loss included, one precision
    # below what the configuration states.
    dtype: Any = jnp.float32


def matmul(x, w):
    return jnp.matmul(x, jnp.asarray(w, x.dtype), precision=_HI)


def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * jnp.asarray(p["scale"], x.dtype)


def rotary(x, theta):
    """``x cos + rotate_half(x) sin`` on ``[batch, seq, heads, d]``:
    column ``i`` and column ``i + d/2`` turn together by ``pos
    theta^(-2i/d)``, positions 0 on."""
    d, s = x.shape[-1], x.shape[1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    angle = np.concatenate([angle, angle], axis=-1)[None, :, None, :]
    cos = jnp.asarray(np.cos(angle), x.dtype)
    sin = jnp.asarray(np.sin(angle), x.dtype)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


def attention(p, x, z: Sizes, windowed, rotated):
    """``windowed`` / ``rotated``: this layer's entries of the two
    layouts, bool scalars (traced: the layers are one scan)."""
    b, s, _ = x.shape
    h, h_kv, d = z.n_heads, z.n_kv_heads, z.head_dim
    q = matmul(x, p["q"]["kernel"]).reshape(b, s, h, d)
    k = matmul(x, p["k"]["kernel"]).reshape(b, s, h_kv, d)
    v = matmul(x, p["v"]["kernel"]).reshape(b, s, h_kv, d)
    q = jnp.where(rotated, rotary(q, z.rope_theta), q)
    k = jnp.where(rotated, rotary(k, z.rope_theta), k)
    shared = jnp.arange(h) // (h // h_kv)  # query head n reads n // ratio
    k, v = k[:, :, shared], v[:, :, shared]  # [b, s, h, d]
    rows = min(z.q_block, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows} query rows")

    @jax.checkpoint
    def attend(_, first):
        """Rows ``first .. first + rows - 1`` against every column."""
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k, precision=_HI)
        scores = scores / math.sqrt(d)
        i = first + jnp.arange(rows)[:, None]
        j = jnp.arange(s)[None, :]
        valid = (i - j >= 0) & ((i - j < z.window) | ~windowed)
        scores = jnp.where(valid, scores, -1e30)
        return None, jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
            precision=_HI,
        )

    _, out = jax.lax.scan(attend, None, jnp.arange(0, s, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)  # [n, b, rows, h, d]
    return matmul(out, p["o"]["kernel"])


def routed_experts(p, u, m, z: Sizes):
    """The held experts' part of the top-k sum over ``m``, chosen and
    weighed from ``u``."""
    probs = jax.nn.softmax(matmul(u, p["router"]), axis=-1)  # [b, s, E]
    picked, chosen = jax.lax.top_k(probs, z.top_k)
    weights = picked / picked.sum(-1, keepdims=True)
    held = p["experts_gate"].shape[0]

    @jax.checkpoint
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(
            jnp.where(chosen == z.first_expert + e, weights, 0.0), axis=-1
        )  # zero where expert e was not chosen
        term = matmul(jax.nn.relu(matmul(m, gate)) * matmul(m, up), down)
        return out + weight[..., None] * term, None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m), (
        jnp.arange(held), p["experts_gate"], p["experts_up"],
        p["experts_down"],
    ))
    return out


def block(p, x, z: Sizes, windowed, rotated):
    u = rms_norm(p["attn_norm"], x, z.eps)
    x = x + attention(p["attn"], u, z, windowed, rotated)
    return x + routed_experts(p, u, rms_norm(p["ffn_norm"], x, z.eps), z)


def cross_entropy(logits, labels):
    """Mean over every entry of ``labels``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def layout(pattern, n_layers: int):
    return jnp.asarray([bool(pattern[i % len(pattern)])
                        for i in range(n_layers)])


def run_blocks(blocks: list, x, z: Sizes):
    """The layers applied in turn, each a ``jax.checkpoint``, as ONE loop
    over their stacked parameters (a whole model unrolled took the chip's
    compiler 45 minutes: PERF.md section 7)."""
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *blocks)
    step = jax.checkpoint(lambda x, layer: (block(layer[0], x, z, *layer[1:]),
                                            None))
    return jax.lax.scan(step, x, (
        stacked, layout(z.window_layout, z.n_layers),
        layout(z.rope_layout, z.n_layers),
    ))[0]


def loss(params, tokens, z: Sizes):
    """``tokens [b, s + 1]``: the mean of CE(logits_i, t_{i+1}) over
    positions ``0 .. s-1``."""
    s = tokens.shape[1] - 1
    table = jnp.asarray(params["embed"]["embedding"], z.dtype)
    x = run_blocks(
        [params[f"block_{i}"] for i in range(z.n_layers)],
        table[tokens[:, :s]], z,
    )

    @jax.checkpoint
    def head_loss(hidden, labels):
        return cross_entropy(
            matmul(rms_norm(params["final_norm"], hidden, z.eps),
                   params["head"]), labels,
        )

    return head_loss(x, tokens[:, 1:])
