"""The latent-attention / routed-expert language model written out in plain
``jax.numpy``: the reference half of ``correct`` for the ``latent_moe_lm``
family.

Float32 throughout, every matmul at ``precision="highest"``, attention as a
masked softmax over the whole score matrix, the routed part as a loop over
the experts held with a ``[T]`` weight that is zero where the expert was
not chosen: no kernel, no row buffer, no index map, no flax, no line of
``horovod_tpu``. It reads the parameter tree the program's modules create
(names below), so both sides start from the same weights.

Equations (no bias anywhere, eps 1e-6). Block::

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))

Attention (H heads; n, r, v the nope, rope and value widths)::

    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> H x [q_nope (n) | q_rope (r)]
    [c_kv | k_r] = x W_kva;  [k_nope | v] = RMSNorm(c_kv) W_kvb -> H x (n + v)
    rotary (theta, adjacent pairs, no scaling) on q_rope and on k_r, which
    every head of a position shares
    out = concat_H(causal softmax([q_nope|q_rope] [k_nope|k_r]^T / sqrt(n + r)) v) W_o

Expert layer (E experts scored, k chosen, ``held`` of them here from
``first`` on)::

    s = sigmoid(x W_r);  chosen = the k largest of s (the score bias is zero)
    w = s_chosen / sum(s_chosen) * scale
    y = sum_{e held} [e chosen] w_e E_e(x) + E_shared(x)
    E(x) = W_d (silu(W_g x) * W_u x)

The terms of experts that are not held are left out, as in the program
(the configuration file's ``deployment``). Dense layer: the same ``E`` at
the dense width. Output: final RMSNorm, untied head. Multi-token module::

    h' = W_eh [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]
    one expert block, its own final RMSNorm, the shared embedding and head
    loss = CE(main, t_{i+1}) + weight CE(mtp, t_{i+2})

Departures from the published model are the configuration file's
``assumed``: this file follows the program where they differ.

So that one micro-batch of 1 x 4096 fits the reference phase, every block
is a ``jax.checkpoint`` and attention runs over groups of heads under
``lax.map``, each group a checkpoint too: the backward holds one group's
``[heads, s, s]`` scores at a time; an expert's term and each head's
logits and loss are checkpoints as well. So that the program compiles in
minutes, the blocks of one structure run as a ``lax.scan`` over their
stacked parameters and the held experts as a ``lax.scan`` over theirs
(:func:`run_blocks`). None of this changes a number.

Parameter tree (``horovod_tpu/models/latent_moe.LatentMoELM``):
``embed/embedding [V, d]``, ``head [d, V]``, ``final_norm/scale``,
``block_<i>/{attn_norm, ffn_norm}/scale``, ``block_<i>/attn/{q_a, q_b,
kv_a, kv_b, o}/kernel``, ``block_<i>/attn/{q_norm, kv_norm}/scale``,
``block_<i>/ffn/{gate, up, down}/kernel`` (dense) or ``block_<i>/ffn/
{router [d, E], experts_gate [held, d, f], experts_up, experts_down
[held, f, d], shared/{gate, up, down}/kernel}``, ``mtp_proj/kernel
[2d, d]``, ``mtp_hidden_norm``, ``mtp_embed_norm``, ``mtp_block/...``,
``mtp_final_norm``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_layers: int
    n_dense_layers: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    rope_theta: float
    first_expert: int
    top_k: int
    routed_scale: float
    n_mtp: int
    mtp_weight: float
    eps: float = 1e-6
    head_group: int = 2  # heads whose scores are live together
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
    """Adjacent pairs of the last axis rotated by ``pos theta^(-2i/d)``;
    ``x`` is ``[batch, seq, ..., d]``."""
    d, s = x.shape[-1], x.shape[1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack(
        [a * cos - b * sin, a * sin + b * cos], axis=-1
    ).reshape(x.shape).astype(x.dtype)


@jax.checkpoint
def _attend(q, k, v):
    """``[b, g, s, *]`` heads: causal softmax(q k^T / sqrt(width)) v."""
    s = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=_HI)
    scores = scores / math.sqrt(q.shape[-1])
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v, precision=_HI
    )


def attention(p, x, z: Sizes):
    b, s, _ = x.shape
    h, n, r, v = z.n_heads, z.qk_nope_dim, z.qk_rope_dim, z.v_dim
    c_q = rms_norm(p["q_norm"], matmul(x, p["q_a"]["kernel"]), z.eps)
    q = matmul(c_q, p["q_b"]["kernel"]).reshape(b, s, h, n + r)
    kv_a = matmul(x, p["kv_a"]["kernel"])
    c_kv, k_r = kv_a[..., :z.kv_lora_rank], kv_a[..., z.kv_lora_rank:]
    kv = matmul(
        rms_norm(p["kv_norm"], c_kv, z.eps), p["kv_b"]["kernel"]
    ).reshape(b, s, h, n + v)
    q = jnp.concatenate(
        [q[..., :n], rotary(q[..., n:], z.rope_theta)], axis=-1
    )
    k_r = rotary(k_r[:, :, None, :], z.rope_theta)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_r, (b, s, h, r))], axis=-1
    )
    g = min(z.head_group, h)
    if h % g:
        raise ValueError(f"{h} heads in groups of {g}")

    def grouped(t):  # [b, s, h, w] -> [h/g, b, g, s, w]
        return jnp.moveaxis(
            t.reshape(b, s, h // g, g, t.shape[-1]), (2, 3), (0, 2)
        )

    out = jax.lax.map(
        lambda qkv: _attend(*qkv),
        (grouped(q), grouped(k), grouped(kv[..., n:])),
    )  # [h/g, b, g, s, v]
    out = jnp.moveaxis(out, (0, 2), (2, 3)).reshape(b, s, h * v)
    return matmul(out, p["o"]["kernel"])


def gated_mlp(x, gate, up, down):
    return matmul(jax.nn.silu(matmul(x, gate)) * matmul(x, up), down)


def routed_experts(p, x, z: Sizes):
    """The held experts' part of the top-k sum, and the shared expert."""
    scores = jax.nn.sigmoid(matmul(x, p["router"]))  # [b, s, E]
    picked, chosen = jax.lax.top_k(scores, z.top_k)
    weights = picked / picked.sum(-1, keepdims=True) * z.routed_scale
    held = p["experts_gate"].shape[0]

    @jax.checkpoint
    def add_expert(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(
            jnp.where(chosen == z.first_expert + e, weights, 0.0), axis=-1
        )  # zero where expert e was not chosen
        return out + weight[..., None] * gated_mlp(x, gate, up, down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(held), p["experts_gate"], p["experts_up"],
        p["experts_down"],
    ))
    if "shared" in p:
        shared = p["shared"]
        out = out + gated_mlp(
            x, shared["gate"]["kernel"], shared["up"]["kernel"],
            shared["down"]["kernel"],
        )
    return out


def block(p, x, z: Sizes):
    x = x + attention(p["attn"], rms_norm(p["attn_norm"], x, z.eps), z)
    h = rms_norm(p["ffn_norm"], x, z.eps)
    ffn = p["ffn"]
    if "router" in ffn:
        return x + routed_experts(ffn, h, z)
    return x + gated_mlp(
        h, ffn["gate"]["kernel"], ffn["up"]["kernel"], ffn["down"]["kernel"]
    )


def cross_entropy(logits, labels):
    """Mean over every entry of ``labels``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def run_blocks(blocks: list, x, z: Sizes):
    """``blocks`` (parameter trees of one structure) applied in turn, each
    a ``jax.checkpoint``, as ONE loop over their stacked parameters: the
    chip's compiler takes seconds for every float32 matmul that stands in
    the program by itself, and a loop's body is compiled once (a whole
    model unrolled took 45 minutes to compile, this takes a few)."""
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *blocks)
    step = jax.checkpoint(lambda x, p: (block(p, x, z), None))
    return jax.lax.scan(step, x, stacked)[0]


def loss(params, tokens, z: Sizes):
    """``tokens [b, s + 1 + n_mtp]``: CE(main, t+1) + weight CE(mtp, t+2)
    over positions ``0 .. s-1``."""
    s = tokens.shape[1] - 1 - z.n_mtp
    table = jnp.asarray(params["embed"]["embedding"], z.dtype)
    x = table[tokens[:, :s]]
    dense = [params[f"block_{i}"] for i in range(z.n_dense_layers)]
    routed = [params[f"block_{i}"]
              for i in range(z.n_dense_layers, z.n_layers)]
    for group in (dense, routed):
        if group:
            x = run_blocks(group, x, z)

    @jax.checkpoint
    def head_loss(norm, hidden, labels):
        return cross_entropy(
            matmul(rms_norm(norm, hidden, z.eps), params["head"]), labels
        )

    total = head_loss(params["final_norm"], x, tokens[:, 1:s + 1])
    if z.n_mtp:
        merged = matmul(jnp.concatenate([
            rms_norm(params["mtp_hidden_norm"], x, z.eps),
            rms_norm(params["mtp_embed_norm"], table[tokens[:, 1:s + 1]],
                     z.eps),
        ], axis=-1), params["mtp_proj"]["kernel"])
        merged = run_blocks([params["mtp_block"]], merged, z)
        total = total + z.mtp_weight * head_loss(
            params["mtp_final_norm"], merged, tokens[:, 2:s + 2]
        )
    return total
