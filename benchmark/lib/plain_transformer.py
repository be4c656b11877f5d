"""The transformer the cells train, written out in plain ``jax.numpy``.

This is the reference half of ``correct``: float32 throughout, matmuls at
``precision="highest"`` (on a TPU a float32 matmul otherwise runs in bf16
passes), XLA attention with the whole score matrix, no kernel, no flax, no
line of the framework. It reads the parameter tree the program's flax
modules create (names below), so both sides start from the same weights.

It follows the program's model, not the papers', where they differ; each
departure from the published architecture is listed under ``assumed`` in
the configuration's file:

* pre-LayerNorm blocks (GPT-2's; published BERT is post-LN);
* LayerNorm epsilon 1e-6 (flax's default; GPT-2 1e-5, BERT 1e-12);
* GELU in its tanh form (GPT-2's ``gelu_new``; published BERT uses erf);
* no dropout.

Parameter tree (``Transformer`` in ``horovod_tpu/models/transformer.py``):
``wte/embedding [V, d]``, ``wpe/embedding [P, d]``, optional
``wtt/embedding [T, d]``, ``block_<i>/{LayerNorm_0, MultiHeadAttention_0/
{query, key, value: kernel [d, H, D], bias [H, D]; out: kernel [H, D, d],
bias [d]}, LayerNorm_1, MlpBlock_0/{Dense_0, Dense_1}}``, ``ln_f``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * _f32(p["scale"]) + _f32(
        p["bias"]
    )


def dense(p, x):
    return jnp.einsum(
        "...i,io->...o", x, _f32(p["kernel"]), precision=_HI
    ) + _f32(p["bias"])


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)
    ))


def attention(p, x, *, causal: bool, key_mask=None):
    """``key_mask`` is ``[batch, seq]``, true where the key is a token."""
    proj = lambda name: jnp.einsum(  # noqa: E731
        "bsd,dhk->bshk", x, _f32(p[name]["kernel"]), precision=_HI
    ) + _f32(p[name]["bias"])
    q, k, v = proj("query"), proj("key"), proj("value")
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=_HI)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    if causal:
        s = scores.shape[-1]
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    if key_mask is not None:
        scores = jnp.where(
            key_mask[:, None, None, :].astype(bool), scores, -1e30
        )
    probs = jax.nn.softmax(scores, axis=-1)
    y = jnp.einsum("bhqs,bshk->bqhk", probs, v, precision=_HI)
    return jnp.einsum(
        "bqhk,hkd->bqd", y, _f32(p["out"]["kernel"]), precision=_HI
    ) + _f32(p["out"]["bias"])


def block(p, x, *, causal: bool, key_mask=None):
    x = x + attention(
        p["MultiHeadAttention_0"], layer_norm(p["LayerNorm_0"], x),
        causal=causal, key_mask=key_mask,
    )
    h = layer_norm(p["LayerNorm_1"], x)
    h = gelu_tanh(dense(p["MlpBlock_0"]["Dense_0"], h))
    return x + dense(p["MlpBlock_0"]["Dense_1"], h)


def hidden_states(p, tokens, *, n_layers: int, causal: bool,
                  token_types=None, key_mask=None):
    """Embeddings, ``n_layers`` blocks, the final LayerNorm."""
    x = _f32(p["wte"]["embedding"])[tokens]
    x = x + _f32(p["wpe"]["embedding"])[: tokens.shape[-1]]
    if token_types is not None:
        x = x + _f32(p["wtt"]["embedding"])[token_types]
    for i in range(n_layers):
        x = block(p[f"block_{i}"], x, causal=causal, key_mask=key_mask)
    return layer_norm(p["ln_f"], x)


def tied_logits(p, hidden):
    return jnp.einsum(
        "bsd,vd->bsv", hidden, _f32(p["wte"]["embedding"]), precision=_HI
    )


def cross_entropy(logits, labels):
    """Mean over every entry of ``labels``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -picked.mean()


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))
