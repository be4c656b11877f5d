"""The Kimi-Delta-Attention / NoPE-latent-attention / routed-expert language
model written out in plain ``jax.numpy``: the reference half of ``correct``
for the ``linear_moe_lm`` family.

Float32 throughout, every matmul at ``precision="highest"``. Kimi Delta
Attention is the RECURRENCE, literally: one ``lax.scan`` step a position
over a ``[H, d, d]`` float32 state, no chunk, no triangle, no inverse. The
convolution is ``conv_size`` shifted multiplies; latent attention a masked
softmax over whole rows of the score matrix; the routed part a loop over
the experts held with a ``[T]`` weight that is zero where the expert was
not chosen. No kernel, no flax, no line of ``horovod_tpu``. It reads the
parameter tree the program's modules create (names below), so both sides
start from the same weights.

Equations (no bias anywhere, eps 1e-5). Pre-norm blocks, ``h' = h +
mixer(RMSNorm(h))``, ``out = h' + ffn(RMSNorm(h'))``. Layer ``l``
(1-indexed) has the mixer the published lists give it.

KDA (``H`` heads, ``d`` key and value channels; ``u = RMSNorm(h)``)::

    q~, k~, v~ = u W_q, u W_k, u W_v
    x^ = SiLU(conv(x~)),  conv: y_t,c = sum_i w_i,c x~_{t - (n - 1) + i, c},
         zeros before t = 0 (depthwise, causal, n taps)
    q_t = q^_t / sqrt(|q^_t|^2 + 1e-6) d^-1/2,  k_t = k^_t / sqrt(|k^_t|^2
         + 1e-6)                                  per head
    g_t = -exp(A_log_h) softplus((u W_fa) W_fb + dt_bias)   [H, d], <= 0
    beta_t = sigmoid(u W_b)                                 [H]
    S' = Diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                                         S_0 = 0
    z_t = RMSNorm_d(o_t) sigmoid((u W_ga) W_gb)             per head
    mixer(h) = concat_heads(z) W_o

Latent layer, NoPE (``H`` heads; ``n``, ``r``, ``v`` the nope, shared and
value widths)::

    q = u W_q -> H x (n + r);  [c_kv | k_r] = u W_kva
    [k_nope | v] = RMSNorm(c_kv) W_kvb -> H x (n + v)
    k = [k_nope | k_r], k_r shared by every head; NOTHING is rotated
    a = softmax_{j<=i}(q_i . k_j / sqrt(n + r)) v_j;  mixer(h) = concat(a) W_o

FFN: the first ``n_dense_layers`` layers SwiGLU at the dense width; every
other layer (``E`` experts scored, ``k`` chosen, ``held`` of them here from
``first`` on)::

    s = sigmoid(m W_r);  C = the k largest of s (the score bias is zero)
    w = s[C] / sum(s[C]) * scale
    ffn(m) = sum_{e held} [e in C] w_e E_e(m) + E_shared(m)
    E(m) = W_d (silu(W_g m) * W_u m)

then a final RMSNorm, an untied head and the mean next-token cross
entropy. The terms of experts that are not held are left out, as in the
program (the configuration file's ``deployment``).

So that one sequence of 8,192 fits the reference phase and compiles in
minutes, nothing of which changes a number: the recurrence runs over
groups of ``scan_group`` positions, each group a ``jax.checkpoint`` (the
backward keeps one state a group and a group's states while it is in it);
attention runs over blocks of ``q_block`` query rows, each a checkpoint;
runs of consecutive layers of one structure are ONE ``lax.scan`` over
their stacked parameters, each layer a checkpoint; the held experts are a
``lax.scan`` over theirs, each term a checkpoint, and so is the head with
its loss.

Parameter tree (``horovod_tpu/models/linear_moe.LinearMoELM``):
``embed/embedding [V, D]``, ``head [D, V]``, ``final_norm/scale``,
``block_<i>/{attn_norm, ffn_norm}/scale``; a KDA layer's
``block_<i>/attn/{q, k, v, o, f_a, g_a}/kernel``, ``f_b``, ``g_b``
``[rank, H d]``, ``b [D, H]``, ``conv_{q,k,v} [taps, H d]``, ``A_log [H]``,
``dt_bias [H d]``, ``o_norm [d]``; a latent layer's ``block_<i>/attn/{q,
kv_a, kv_b, o}/kernel``, ``kv_norm/scale``; ``block_<i>/ffn/{gate, up,
down}/kernel`` (dense) or ``block_<i>/ffn/{router [D, E], experts_gate
[held, D, F], experts_up, experts_down [held, F, D], shared/{gate, up,
down}/kernel}``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_layers: int
    n_dense_layers: int
    kda_layers: Tuple[int, ...]  # 1-indexed, as published
    kda_heads: int
    kda_head_dim: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    first_expert: int
    top_k: int
    routed_scale: float
    eps: float = 1e-5
    l2_eps: float = 1e-6
    scan_group: int = 128  # positions of the recurrence a checkpoint
    q_block: int = 256  # query rows whose scores are live together
    # The reference is this file in float32. Any other dtype is a CONTROL
    # (``benchmark/controls.py``): the embedding is read in it and every
    # operation follows its operand, so bfloat16 here is the whole model,
    # state, decays, router, softmax, norms, logits and loss included, one
    # precision below what the configuration states.
    dtype: Any = jnp.float32
    # Further controls, each ONE departure from the equations above:
    # "no_decay" (g = 0), "beta_one" (beta = 1), "no_conv" (x^ = SiLU(x~))
    departure: str = ""


def matmul(x, w):
    return jnp.matmul(x, jnp.asarray(w, x.dtype), precision=_HI)


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * jnp.asarray(scale, x.dtype)


def conv_silu(x, taps, z: Sizes):
    """``x [b, s, w]``, ``taps [n, w]``: tap ``n - 1`` meets the position
    itself, tap 0 the position ``n - 1`` before it."""
    if z.departure == "no_conv":
        return jax.nn.silu(x)
    n, s = taps.shape[0], x.shape[1]
    taps = jnp.asarray(taps, x.dtype)
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    y = taps[0] * padded[:, 0:s]
    for i in range(1, n):
        y = y + taps[i] * padded[:, i:i + s]
    return jax.nn.silu(y)


def delta_rule(q, k, v, g, beta, z: Sizes):
    """The recurrence over ``[b, s, H, d]`` operands (``beta [b, s, H]``),
    a position a step; returns ``o [b, s, H, d]``."""
    b, s, h, d = q.shape

    def step(state, x):  # state [b, H, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]  # S' = Diag(exp g) S
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HI)
        write = beta_t[..., None] * (v_t - read)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, write, precision=_HI)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HI)

    group = min(z.scan_group, s)
    if s % group:
        raise ValueError(f"{s} positions in groups of {group}")
    by_group = lambda x: jnp.moveaxis(x, 1, 0).reshape(  # noqa: E731
        s // group, group, *x.shape[:1], *x.shape[2:]
    )
    run = jax.checkpoint(lambda state, x: jax.lax.scan(step, state, x))
    state = jnp.zeros((b, h, d, d), q.dtype)
    _, out = jax.lax.scan(run, state, tuple(map(by_group, (q, k, v, g, beta))))
    return jnp.moveaxis(out.reshape(s, b, h, d), 0, 1)


def kda(p, u, z: Sizes):
    b, s, _ = u.shape
    h, d = z.kda_heads, z.kda_head_dim
    heads = lambda x: x.reshape(b, s, h, d)  # noqa: E731
    q, k, v = (
        heads(conv_silu(matmul(u, p[x]["kernel"]), p[f"conv_{x}"], z))
        for x in "qkv"
    )
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + z.l2_eps
    )
    q, k = unit(q) * d ** -0.5, unit(k)
    pre = matmul(matmul(u, p["f_a"]["kernel"]), p["f_b"])
    g = -jnp.exp(jnp.asarray(p["A_log"], u.dtype))[:, None] * heads(
        jax.nn.softplus(pre + jnp.asarray(p["dt_bias"], u.dtype))
    )
    beta = jax.nn.sigmoid(matmul(u, p["b"]))  # [b, s, H]
    if z.departure == "no_decay":
        g = jnp.zeros_like(g)
    if z.departure == "beta_one":
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, g, beta, z)
    gate = jax.nn.sigmoid(matmul(matmul(u, p["g_a"]["kernel"]), p["g_b"]))
    out = rms_norm(p["o_norm"], o, z.eps).reshape(b, s, h * d) * gate
    return matmul(out, p["o"]["kernel"])


def latent_attention(p, u, z: Sizes):
    b, s, _ = u.shape
    h, n, r, v = z.n_heads, z.qk_nope_dim, z.qk_rope_dim, z.v_dim
    q = matmul(u, p["q"]["kernel"]).reshape(b, s, h, n + r)
    kv_a = matmul(u, p["kv_a"]["kernel"])
    c_kv, k_r = kv_a[..., :z.kv_lora_rank], kv_a[..., z.kv_lora_rank:]
    kv = matmul(
        rms_norm(p["kv_norm"]["scale"], c_kv, z.eps), p["kv_b"]["kernel"]
    ).reshape(b, s, h, n + v)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_r[:, :, None], (b, s, h, r))],
        axis=-1,
    )
    values = kv[..., n:]
    rows = min(z.q_block, s)
    if s % rows:
        raise ValueError(f"{s} positions in blocks of {rows} query rows")

    @jax.checkpoint
    def attend(_, first):
        """Rows ``first .. first + rows - 1`` against every column."""
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, rows, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k, precision=_HI)
        scores = scores / math.sqrt(n + r)
        i = first + jnp.arange(rows)[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= i, scores, -1e30)
        return None, jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), values,
            precision=_HI,
        )

    _, out = jax.lax.scan(attend, None, jnp.arange(0, s, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * v)  # [n, b, rows, h, v]
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


def block(p, x, z: Sizes, mixer):
    u = rms_norm(p["attn_norm"]["scale"], x, z.eps)
    x = x + mixer(p["attn"], u, z)
    m = rms_norm(p["ffn_norm"]["scale"], x, z.eps)
    ffn = p["ffn"]
    if "router" in ffn:
        return x + routed_experts(ffn, m, z)
    return x + gated_mlp(
        m, ffn["gate"]["kernel"], ffn["up"]["kernel"], ffn["down"]["kernel"]
    )


def run_blocks(blocks: list, x, z: Sizes, mixer):
    """``blocks`` (parameter trees of one structure) applied in turn, each
    a ``jax.checkpoint``, as ONE loop over their stacked parameters (a
    whole model unrolled took the chip's compiler 45 minutes: PERF.md
    section 7)."""
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *blocks)
    step = jax.checkpoint(lambda x, p: (block(p, x, z, mixer), None))
    return jax.lax.scan(step, x, stacked)[0]


def layer_runs(z: Sizes) -> list:
    """``[(first, last + 1, mixer)]``: runs of consecutive layers
    (0-indexed) with one mixer and one kind of FFN."""
    kinds = [(kda if i + 1 in z.kda_layers else latent_attention,
              i < z.n_dense_layers) for i in range(z.n_layers)]
    runs, first = [], 0
    for i in range(1, z.n_layers + 1):
        if i == z.n_layers or kinds[i] != kinds[first]:
            runs.append((first, i, kinds[first][0]))
            first = i
    return runs


def cross_entropy(logits, labels):
    """Mean over every entry of ``labels``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def loss(params, tokens, z: Sizes):
    """``tokens [b, s + 1]``: the mean of CE(logits_i, t_{i+1}) over
    positions ``0 .. s-1``."""
    s = tokens.shape[1] - 1
    table = jnp.asarray(params["embed"]["embedding"], z.dtype)
    x = table[tokens[:, :s]]
    for first, end, mixer in layer_runs(z):
        x = run_blocks(
            [params[f"block_{i}"] for i in range(first, end)], x, z, mixer
        )

    @jax.checkpoint
    def head_loss(hidden, labels):
        return cross_entropy(
            matmul(rms_norm(params["final_norm"]["scale"], hidden, z.eps),
                   params["head"]), labels,
        )

    return head_loss(x, tokens[:, 1:])
