"""The learned-sparse-attention, grouped-head, routed-expert language model
written out in plain ``jax.numpy``: the reference half of ``correct`` for
the ``select_moe_lm`` family.

Float32 throughout, every matmul at ``precision="highest"``. The indexer's
scores ``I`` are built WHOLE for a layer (``[S, S]``, 268 MB at 8,192), each
row's threshold is read off a full sort of the row, the kept set is ``I >=
tau`` under the causal mask, and attention is a masked softmax over whole
rows of the score matrix: no kernel, no bisection, no tile, no shared K/V
block, no flax, no line of ``horovod_tpu``. It reads the parameter tree the
program's modules create (names below), so both sides start from the same
weights. ``matmul``, ``rms_norm``, ``rotary`` and ``cross_entropy`` are
``plain_window_moe``'s.

Equations (no bias but the indexer's LayerNorm, eps 1e-6). Layer input ``h``
``[S, D]``, ``H`` query heads on ``H_kv`` K/V heads of width ``d``, an
indexer of ``H_I`` heads of ``d_I`` on one key head::

    u = RMSNorm1(h)
    q = RMSNorm_d(u W_q);  k = RMSNorm_d(u W_k);  v = u W_v
    q, k <- q cos + rotate_half(q) sin   (column i pairs with i + d/2)
    x = stop_gradient(u)
    qI = x W_qI;  kI = LayerNorm(x W_kI);  w = (x W_wI) H_I^-1/2 d_I^-1/2
    qI, kI <- the same rotation over their d_I columns
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])               s <= t
    tau_t   = the topk-th largest of I[t, 0..t], -inf under topk entries
    sel(t)  = { s <= t : I[t, s] >= tau_t }      no gradient passes it
    p_n[t, .] = softmax_{s in sel(t)}(q_n[t] . k[s] / sqrt(d))
    a_n[t]  = sum_s p_n[t, s] v[s];  head n reads K/V head n // (H / H_kv)
    h' = h + concat(a) W_o
    m = RMSNorm2(h');  r = m W_r;  C = the k largest of r;  g = softmax(r[C])
    out = h' + sum_{e held} [e in C] g_e W_down,e (silu(W_gate,e m) * W_up,e m)
    pbar[t, s] = stop_gradient(mean_n p_n[t, s])
    L_I = mean_t sum_{s in sel(t)} pbar (log pbar - log softmax_sel(I[t])[s])

then a final RMSNorm, an untied head, and ``loss = CE + sum_layers L_I``.
The terms of experts that are not held are left out, as in the program (the
configuration file's ``deployment``).

Departures from the report (DeepSeek-V3.2-Exp), each the configuration
file's ``assumed``: the indexer reads the layer's normed input for its
queries too (the report's come from a low-rank query this model does not
have); ties at ``tau`` are all kept; ``L_I`` enters with coefficient 1 and
is taken over every layer in one stage (no dense warm-up); the rotary
angles are tabulated in float64 and rounded once.

So that one sequence of 8,192 fits the reference phase, nothing of which
changes a number: the layers run as ONE ``lax.scan`` over their stacked
parameters, each a ``jax.checkpoint``; ``I`` is made in blocks of
``q_block`` query rows (16 heads of ``[q_block, S]`` dots live at a time)
and set side by side; attention and the index loss run over the same
blocks under ``lax.scan``, each block a checkpoint that makes its rows of
``I`` again for the loss's gradient and reads its rows of the kept set from
the whole one; the held experts and the head with its loss are scans of
checkpoints as in ``plain_window_moe``.

Parameter tree (``horovod_tpu/models/window_moe.WindowMoELM`` with the
indexer on): ``embed/embedding``, ``head``, ``final_norm/scale``,
``block_<i>/{attn_norm, ffn_norm}/scale``, ``block_<i>/attn/{q, k, v, o,
index_q, index_k}/kernel``, ``block_<i>/attn/{q_norm, k_norm}/scale``,
``block_<i>/attn/index_k_norm/{scale, bias}``, ``block_<i>/attn/index_w``,
``block_<i>/{router, experts_gate, experts_up, experts_down}``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from .plain_window_moe import (
    _HI, cross_entropy, matmul, rms_norm, rotary,
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    index_heads: int
    index_head_dim: int
    index_top_k: int
    rope_theta: float
    first_expert: int
    top_k: int
    eps: float = 1e-6
    q_block: int = 256  # query rows whose scores are live together
    # The reference is this file in float32. Any other dtype is a CONTROL
    # (``benchmark/controls.py``): bfloat16 here is the whole model, the
    # indexer's scores, the threshold and the selection included.
    dtype: Any = jnp.float32
    # "" or a control's departure: "keep_all" (selection ignored: every
    # earlier position attended to and in the index loss)
    departure: str = ""


def layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * jnp.asarray(p["scale"], x.dtype) + jnp.asarray(p["bias"], x.dtype))


def in_blocks(fn, s: int, rows: int):
    """``fn(first)`` for every block of ``rows`` query rows, stacked."""
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows} query rows")
    return jax.lax.map(fn, jnp.arange(0, s, rows))


def side_by_side(blocks, b: int, s: int):
    """``[n, b, rows, ...] -> [b, s, ...]``."""
    return jnp.moveaxis(blocks, 0, 1).reshape((b, s) + blocks.shape[3:])


def attention(p, u, z: Sizes):
    """``(concat(a) W_o, L_I)`` of one layer."""
    b, s, _ = u.shape
    h, h_kv, d = z.n_heads, z.n_kv_heads, z.head_dim
    by_head = lambda t, n: t.reshape(b, s, n, -1)  # noqa: E731
    q = rms_norm(p["q_norm"], by_head(matmul(u, p["q"]["kernel"]), h), z.eps)
    k = rms_norm(p["k_norm"], by_head(matmul(u, p["k"]["kernel"]), h_kv),
                 z.eps)
    v = by_head(matmul(u, p["v"]["kernel"]), h_kv)
    q, k = rotary(q, z.rope_theta), rotary(k, z.rope_theta)
    shared = jnp.arange(h) // (h // h_kv)  # query head n reads n // ratio
    k, v = k[:, :, shared], v[:, :, shared]  # [b, s, h, d]

    x = jax.lax.stop_gradient(u)
    n_i, d_i = z.index_heads, z.index_head_dim
    q_i = rotary(by_head(matmul(x, p["index_q"]["kernel"]), n_i),
                 z.rope_theta)
    k_i = rotary(
        layer_norm(p["index_k_norm"], matmul(x, p["index_k"]["kernel"]),
                   z.eps)[:, :, None, :], z.rope_theta,
    )[:, :, 0, :]
    w = matmul(x, p["index_w"]) * jnp.asarray(
        n_i ** -0.5 * d_i ** -0.5, x.dtype
    )
    rows = min(z.q_block, s)
    cols = jnp.arange(s)[None, :]

    def index_rows(first, q_i=q_i, k_i=k_i, w=w):
        """``I[first .. first + rows - 1, :]``, ``[b, rows, s]``."""
        take = lambda t: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, first, rows, axis=1
        )
        dots = jnp.einsum("bqhd,bkd->bhqk", take(q_i), k_i, precision=_HI)
        return jnp.einsum("bqh,bhqk->bqk", take(w), jax.nn.relu(dots),
                          precision=_HI)

    # the whole of I, its rows' thresholds from a sort, the kept set: all
    # constants of the step
    causal = jnp.arange(s)[:, None] >= cols  # [s, s]
    constant = [jax.lax.stop_gradient(t) for t in (q_i, k_i, w)]
    scores = jnp.where(causal, side_by_side(
        in_blocks(lambda first: index_rows(first, *constant), s, rows), b, s
    ), -jnp.inf)
    if z.departure == "keep_all" or z.index_top_k > s:
        kept = jnp.broadcast_to(causal, scores.shape)
    else:
        tau = jnp.sort(scores, axis=-1)[..., s - z.index_top_k]
        kept = causal & (scores >= tau[..., None])
    del scores

    @jax.checkpoint
    def attend(_, first):
        take = lambda t: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            t, first, rows, axis=1
        )
        valid = take(kept)  # [b, rows, s]
        dots = jnp.einsum("bqhd,bkhd->bhqk", take(q), k, precision=_HI)
        probs = jax.nn.softmax(
            jnp.where(valid[:, None], dots / math.sqrt(d), -jnp.inf), axis=-1
        )
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HI)
        target = jax.lax.stop_gradient(probs.mean(axis=1))  # [b, rows, s]
        logq = jax.nn.log_softmax(
            jnp.where(valid, index_rows(first), -jnp.inf), axis=-1
        )
        positive = target > 0
        term = jnp.where(
            positive,
            target * (jnp.log(jnp.where(positive, target, 1))
                      - jnp.where(valid, logq, 0)),
            0,
        )
        return None, (out, term.sum().astype(jnp.float32))

    _, (out, terms) = jax.lax.scan(attend, None, jnp.arange(0, s, rows))
    out = side_by_side(out, b, s).reshape(b, s, h * d)
    return matmul(out, p["o"]["kernel"]), terms.sum() / (b * s)


def routed_experts(p, m, z: Sizes):
    """The held experts' part of the top-k sum over ``m``, chosen and
    weighed from ``m``."""
    logits = matmul(m, p["router"])  # [b, s, E]
    picked, chosen = jax.lax.top_k(logits, z.top_k)
    weights = jax.nn.softmax(picked, axis=-1)
    held = p["experts_gate"].shape[0]

    @jax.checkpoint
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(
            jnp.where(chosen == z.first_expert + e, weights, 0.0), axis=-1
        )  # zero where expert e was not chosen
        term = matmul(jax.nn.silu(matmul(m, gate)) * matmul(m, up), down)
        return out + weight[..., None] * term, None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m), (
        jnp.arange(held), p["experts_gate"], p["experts_up"],
        p["experts_down"],
    ))
    return out


def block(p, x, z: Sizes):
    a, index_loss = attention(p["attn"], rms_norm(p["attn_norm"], x, z.eps), z)
    x = x + a
    return x + routed_experts(p, rms_norm(p["ffn_norm"], x, z.eps), z), index_loss


def loss_parts(params, tokens, z: Sizes):
    """``(cross entropy, sum over layers of L_I)``; ``tokens [b, s + 1]``."""
    s = tokens.shape[1] - 1
    table = jnp.asarray(params["embed"]["embedding"], z.dtype)
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"block_{i}"] for i in range(z.n_layers)],
    )
    x, index_losses = jax.lax.scan(
        jax.checkpoint(lambda x, layer: block(layer, x, z)),
        table[tokens[:, :s]], stacked,
    )

    @jax.checkpoint
    def head_loss(hidden, labels):
        return cross_entropy(
            matmul(rms_norm(params["final_norm"], hidden, z.eps),
                   params["head"]), labels,
        )

    return head_loss(x, tokens[:, 1:]), index_losses.sum()


def loss(params, tokens, z: Sizes):
    ce, index_loss = loss_parts(params, tokens, z)
    return ce + index_loss
