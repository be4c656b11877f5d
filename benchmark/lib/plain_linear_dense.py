"""The Gated-DeltaNet / full-attention / dense-FFN language model written
out in plain ``jax.numpy``: the reference half of ``correct`` for the
``linear_dense_lm`` family.

Float32 throughout, every matmul at ``precision="highest"``. Gated DeltaNet
is the RECURRENCE, literally: one ``lax.scan`` step a position over a ``[H,
d_k, d_v]`` float32 state, no chunk, no triangle, no inverse. The
convolution is ``n`` shifted multiplies; attention a masked softmax over
whole rows of the score matrix. No kernel, no flax, no line of
``horovod_tpu`` (five plain helpers are ``plain_linear_moe``'s). It reads the parameter tree the program's modules create
(names below), so both sides start from the same weights, and it is given
the same share: the heads and vocabulary rows the tree holds.

Equations (no bias anywhere). Blocks norm each sub-layer's OUTPUT::

    h' = h + RMSNorm(mixer(h));  out = h' + RMSNorm(ffn(h'))
    ffn(m) = W_d (silu(W_g m) * W_u m)

Layer ``l`` has the mixer ``layer_types[l]`` names. Gated DeltaNet (``H``
heads held, ``d_k`` key and ``d_v`` value channels; input ``u``)::

    q~, k~, v~, z = u W_q, u W_k, u W_v, u W_z
    x = SiLU(conv(x~)),  conv: y_t,c = sum_i w_i,c x~_{t - (n - 1) + i, c},
        zeros before t = 0 (depthwise, causal, n taps)      for q, k, v
    q^_t = q_t / sqrt(|q_t|^2 + 1e-6) d_k^-1/2,  k^_t = k_t / sqrt(|k_t|^2
        + 1e-6)                                              per head
    g_t = -exp(A_log_h) softplus(u_t w_a,h + dt_bias_h)      [H], <= 0
    beta_t = 2 sigmoid(u_t w_b,h)                            [H]
    S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k^_t (v_t - S'^T k^_t)^T
        (= exp(g_t) S_{t-1} (I - beta_t k^ k^T) + beta_t v k^T, transposed)
    o_t = S_t^T q^_t                                         S_0 = 0
    mixer(u) = concat_heads(RMSNorm_dv(o) * SiLU(z)) W_o

Full attention (``H`` heads held, ``d`` wide, nothing rotated)::

    q, k = RMSNorm_{H d}(u W_q), RMSNorm_{H d}(u W_k)   over the WHOLE held
        projection, one learned scale a column;  v = u W_v
    a = softmax_{j<=i}(q_i . k_j / sqrt(d)) v_j;  mixer(u) = concat(a) W_o

then a final RMSNorm, an untied head and the mean next-token cross
entropy. What the heads that are not held would add is left out, as in the
program (the configuration file's ``deployment``).

So that one sequence of 8,192 fits the reference phase and compiles in
minutes, nothing of which changes a number: the recurrence runs over
groups of ``scan_group`` positions, each group a ``jax.checkpoint``;
attention over blocks of ``q_block`` query rows, each a checkpoint; each
layer is a checkpoint, and so is the head with its loss.

Parameter tree (``horovod_tpu/models/linear_dense.LinearDenseLM``):
``embed/embedding [V, D]``, ``head [D, V]``, ``final_norm/scale``,
``block_<i>/{attn_norm, ffn_norm}/scale``, ``block_<i>/ffn/{gate, up,
down}/kernel``; a Gated DeltaNet layer's ``block_<i>/attn/{q, k, v, z,
o}/kernel``, ``a``, ``b`` ``[D, H]``, ``conv_{q,k,v} [taps, H d]``,
``A_log [H]``, ``dt_bias [H]``, ``o_norm [d_v]``; a full layer's
``block_<i>/attn/{q, k, v, o}/kernel``, ``{q_norm, k_norm}/scale [H d]``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

# the same plain pieces, written once: x w at highest precision, RMSNorm,
# the depthwise causal convolution with SiLU (it reads ``z.departure``
# alone), SwiGLU, the mean cross entropy
from .plain_linear_moe import (  # noqa: F401  (cross_entropy: the tests')
    conv_silu, cross_entropy, gated_mlp, matmul, rms_norm,
)

_HI = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Sizes:
    layer_types: Tuple[str, ...]  # one entry a layer built
    heads: int  # held, of either mixer
    head_dim: int
    key_dim: int
    value_dim: int
    eps: float = 1e-6
    l2_eps: float = 1e-6
    scan_group: int = 128  # positions of the recurrence a checkpoint
    q_block: int = 256  # query rows whose scores are live together
    # The reference is this file in float32. Any other dtype is a CONTROL
    # (``benchmark/controls.py``): the embedding is read in it and every
    # operation follows its operand, so bfloat16 here is the whole model,
    # state, decay, softmax, norms, logits and loss included, one precision
    # below what the configuration states.
    dtype: Any = jnp.float32
    # Further controls, each ONE departure from the equations above:
    # "no_decay" (g = 0), "beta_unscaled" (beta = sigmoid, no factor 2),
    # "no_conv" (x = SiLU(x~)), "no_qk_norm" (q, k as projected)
    departure: str = ""


def delta_rule(q, k, v, g, beta, z: Sizes):
    """The recurrence over ``q, k [b, s, H, d_k]``, ``v [b, s, H, d_v]``,
    ``g, beta [b, s, H]``, a position a step; returns ``o [b, s, H, d_v]``."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, x):  # state [b, H, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]  # S' = exp(g) S
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
    state = jnp.zeros((b, h, dk, dv), q.dtype)
    _, out = jax.lax.scan(run, state, tuple(map(by_group, (q, k, v, g, beta))))
    return jnp.moveaxis(out.reshape(s, b, h, dv), 0, 1)


def gated_delta_net(p, u, z: Sizes):
    b, s, _ = u.shape
    h, dk, dv = z.heads, z.key_dim, z.value_dim
    q, k, v = (
        conv_silu(matmul(u, p[x]["kernel"]), p[f"conv_{x}"], z).reshape(
            b, s, h, -1
        )
        for x in "qkv"
    )
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + z.l2_eps
    )
    q, k = unit(q) * dk ** -0.5, unit(k)
    g = -jnp.exp(jnp.asarray(p["A_log"], u.dtype)) * jax.nn.softplus(
        matmul(u, p["a"]) + jnp.asarray(p["dt_bias"], u.dtype)
    )  # [b, s, H]
    beta = jax.nn.sigmoid(matmul(u, p["b"]))
    if z.departure != "beta_unscaled":
        beta = 2.0 * beta
    if z.departure == "no_decay":
        g = jnp.zeros_like(g)
    o = delta_rule(q, k, v, g, beta, z)
    gate = jax.nn.silu(matmul(u, p["z"]["kernel"]))
    out = rms_norm(p["o_norm"], o, z.eps).reshape(b, s, h * dv) * gate
    return matmul(out, p["o"]["kernel"])


def full_attention(p, u, z: Sizes):
    b, s, _ = u.shape
    h, d = z.heads, z.head_dim
    q, k, v = (matmul(u, p[x]["kernel"]) for x in "qkv")
    if z.departure != "no_qk_norm":
        q = rms_norm(p["q_norm"]["scale"], q, z.eps)
        k = rms_norm(p["k_norm"]["scale"], k, z.eps)
    q, k, v = (x.reshape(b, s, h, d) for x in (q, k, v))
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
        scores = jnp.where(jnp.arange(s)[None, :] <= i, scores, -1e30)
        return None, jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
            precision=_HI,
        )

    _, out = jax.lax.scan(attend, None, jnp.arange(0, s, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)  # [n, b, rows, h, d]
    return matmul(out, p["o"]["kernel"])


def block(p, x, z: Sizes, mixer):
    x = x + rms_norm(p["attn_norm"]["scale"], mixer(p["attn"], x, z), z.eps)
    ffn = p["ffn"]
    return x + rms_norm(p["ffn_norm"]["scale"], gated_mlp(
        x, ffn["gate"]["kernel"], ffn["up"]["kernel"], ffn["down"]["kernel"]
    ), z.eps)


MIXERS = {LINEAR: gated_delta_net, FULL: full_attention}


def loss(params, tokens, z: Sizes):
    """``tokens [b, s + 1]``: the mean of CE(logits_i, t_{i+1}) over
    positions ``0 .. s-1``."""
    s = tokens.shape[1] - 1
    table = jnp.asarray(params["embed"]["embedding"], z.dtype)
    x = table[tokens[:, :s]]
    for i, kind in enumerate(z.layer_types):
        # each layer a checkpoint, the layers one after another: stacking
        # their parameters for a ``lax.scan`` would copy them (and their
        # gradients) once more, 5.4 GB the reference phase does not have
        x = jax.checkpoint(
            lambda x, p, mixer=MIXERS[kind]: block(p, x, z, mixer)
        )(x, params[f"block_{i}"])

    @jax.checkpoint
    def head_loss(hidden, labels):
        return cross_entropy(
            matmul(rms_norm(params["final_norm"]["scale"], hidden, z.eps),
                   params["head"]), labels,
        )

    return head_loss(x, tokens[:, 1:])
