"""Shared transformer core (GPT-2 / BERT / ViT build on this).

TPU-first choices: bf16 compute with fp32 params and fp32 attention
softmax; static shapes; heads and model dims kept MXU-friendly (multiples
of 128 where it matters); optional per-block rematerialization
(``jax.checkpoint``) to trade FLOPs for HBM on long sequences. The
attention implementation is pluggable so the sequence-parallel ring
attention (``horovod_tpu.parallel.sp``) can slot in.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..context import device_platform
from ..ops import actquant as _actquant
from ..ops.fp8 import fp8_dot_general_cls
from ..ops.remat import remat_module


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    max_len: int = 1024
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    causal: bool = True
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    # Per-block rematerialization: False/'none' (off), True/'full'
    # (checkpoint everything), a named jax.checkpoint_policies policy
    # ('dots_saveable' keeps matmul outputs resident and recomputes only
    # elementwise chains), or a custom policy callable — ONE knob shared
    # with dp.make_train_step(remat=...) via ops/remat.resolve_policy.
    remat: Any = False
    # Training matmul precision: None (HVDTPU_COMPUTE_DTYPE decides at
    # init/apply), '' (the model dtype), or 'fp8' — every Dense/
    # DenseGeneral in attention and the MLP gets an ops/fp8
    # Fp8DotGeneral injected (e4m3 fwd, e5m2 grads, delayed scaling;
    # state rides params). Embeddings, LayerNorms and the tied LM head
    # stay in the model dtype.
    compute_dtype: Optional[str] = None
    # extra embeddings for BERT-style models
    type_vocab_size: int = 0
    # Pallas blockwise attention (ops/pallas_kernels.py) — the memory-
    # efficient path for long sequences; dense masks fall back to XLA.
    # None = auto: on where the world's devices are TPUs
    # (context.device_platform), off elsewhere; True off-TPU means the
    # Pallas interpreter, which is for tests, not speed.
    use_flash: Optional[bool] = None


def dot_product_attention(q, k, v, *, causal: bool, mask=None):
    """Plain attention; softmax in fp32 (TPU numerics convention)."""
    d = q.shape[-1]
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(d)
    if causal:
        qlen, klen = scores.shape[-2], scores.shape[-1]
        cmask = jnp.tril(jnp.ones((qlen, klen), jnp.bool_))
        scores = jnp.where(cmask, scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


def _packed_dot(y, kernel, dimension_numbers, precision=None,
                preferred_element_type=None):
    """The out projection's dot on the flash kernels' packed output:
    ``[B, S, H, D] x [H, D, M]`` over ``(H, D)`` as ``[B, S, H D] x [H D,
    M]``, y as the kernels wrote it.  The same parameter tree and the same
    numbers; what changes is what XLA lays out: a 4-D y whose minor
    dimension of 64 half-fills the lanes is kept S-minor, so the kernels'
    ``out`` and its cotangent were each turned at the kernels' door, and
    the backward kernels' row-major ``out`` would be a second residual
    beside the turned one (PERF.md, PR 49)."""
    del dimension_numbers  # DenseGeneral's, for the 4-D operands
    b, s = y.shape[:2]
    return jax.lax.dot_general(
        y.reshape(b, s, -1), kernel.reshape(-1, kernel.shape[-1]),
        (((2,), (0,)), ((), ())), precision=precision,
        preferred_element_type=preferred_element_type,
    )


class MultiHeadAttention(nn.Module):
    cfg: TransformerConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        head_dim = cfg.d_model // cfg.n_heads
        dg_cls = fp8_dot_general_cls(cfg.compute_dtype)
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (cfg.n_heads, head_dim), dtype=cfg.dtype, name=name,
            dot_general_cls=dg_cls,
        )
        out = lambda axis, dot=None: nn.DenseGeneral(  # noqa: E731
            cfg.d_model, axis=axis, dtype=cfg.dtype, name="out",
            dot_general_cls=dg_cls, dot_general=dot,
        )
        with jax.named_scope("attn_proj"):
            q, k, v = dense("query")(x), dense("key")(x), dense("value")(x)
        attn = self.attention_fn
        if attn is None:
            use_flash = cfg.use_flash
            if use_flash is None:
                use_flash = device_platform() == "tpu"
            if use_flash and mask is None and head_dim % 64 == 0:
                from ..ops.pallas_kernels import flash_attention

                # Packed ("bsm") path: merge the minor [H, D] dims and hand
                # the kernel [B, S, H*D], its native packed layout (heads
                # sliced from the lane axis inside).  Not free where D is 64:
                # XLA lays the 4-D projections out S-minor and sets 96
                # transposing copies a step at the kernels' doors (PERF.md,
                # PR 49).  The r4 head-major variant moveaxis'd to [B,H,S,D]
                # and its projection dots ran at ~43% of MXU peak (before
                # PR 1).  Mosaic lane slicing needs 64-aligned offsets, so
                # head_dim % 64 != 0 keeps the head-major path below.
                b, s = q.shape[0], q.shape[1]
                with jax.named_scope("attn_layout"):
                    q, k, v = (
                        t.reshape(b, s, cfg.d_model) for t in (q, k, v)
                    )
                y = flash_attention(
                    q, k, v, causal=cfg.causal, layout="bsm",
                    n_heads=cfg.n_heads,
                )
                with jax.named_scope("attn_layout"):
                    y = y.reshape(b, s, cfg.n_heads, head_dim)
                with jax.named_scope("attn_proj"):
                    return out((-2, -1), _packed_dot)(y)
            if use_flash and mask is None:
                from ..ops.pallas_kernels import flash_attention

                # Head-major fallback for lane-unaligned head dims.
                with jax.named_scope("attn_layout"):
                    q, k, v = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
                y = flash_attention(
                    q, k, v, causal=cfg.causal, layout="bhsd"
                )
                with jax.named_scope("attn_proj"):
                    return out((1, 3))(y)
            with jax.named_scope("attn_xla"):
                y = dot_product_attention(
                    q, k, v, causal=cfg.causal, mask=mask
                )
        else:
            # a caller's attention (ring attention over the sequence axis)
            # names itself: it may hold kernels, which carry no part
            y = attn(q, k, v, causal=cfg.causal, mask=mask)
        with jax.named_scope("attn_proj"):
            return out((-2, -1))(y)


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dg_cls = fp8_dot_general_cls(cfg.compute_dtype)
        with jax.named_scope("mlp"):
            h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, dot_general_cls=dg_cls)(x)
            h = nn.gelu(h)
            return nn.Dense(
                cfg.d_model, dtype=cfg.dtype, dot_general_cls=dg_cls
            )(h)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``, no bias; computed in fp32
    and returned in ``dtype``. The norm of the latent-attention / expert
    blocks (``models/latent_moe.py``); ``Block`` keeps its LayerNorm."""

    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), jnp.float32
        )
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.eps
        )
        return (y * scale).astype(self.dtype)


class GatedMlp(nn.Module):
    """SwiGLU feed-forward, no bias: ``down(silu(gate(x)) * up(x))`` at
    width ``d_ff``. A dense layer's FFN and a shared expert are this
    module; the routed experts are the same function on stacked weights
    (``parallel/ep.local_experts``)."""

    d_ff: int
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name,
            kernel_init=self.kernel_init,
        )
        with jax.named_scope("mlp"):
            h = nn.silu(dense(self.d_ff, "gate")(x)) * dense(
                self.d_ff, "up"
            )(x)
            return dense(x.shape[-1], "down")(h)


class Block(nn.Module):
    """Pre-LN transformer block (GPT-2 style; BERT uses it too here —
    pre-LN trains more stably and the parity target is capability, not
    checkpoint compatibility)."""

    cfg: TransformerConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        with jax.named_scope("norm"):
            h = nn.LayerNorm(dtype=cfg.dtype)(x)
        h = MultiHeadAttention(cfg, attention_fn=self.attention_fn)(h, mask)
        with jax.named_scope("norm"):
            x = x + h
            h = nn.LayerNorm(dtype=cfg.dtype)(x)
        h = MlpBlock(cfg)(h)
        with jax.named_scope("norm"):
            return x + h


class Transformer(nn.Module):
    """Token+position embeddings → N blocks → final LN; returns hidden
    states ``[batch, seq, d_model]``."""

    cfg: TransformerConfig
    attention_fn: Optional[Callable] = None
    lm_head: bool = False  # tied LM head: logits = hidden @ wte.T

    @nn.compact
    def __call__(self, tokens, *, token_types=None, mask=None,
                 return_hidden=False):
        """``return_hidden=True`` skips the tied LM head and returns the
        final-LN hidden states — callers pair it with
        ``ops.losses.fused_cross_entropy`` (logits never materialized;
        same params either way, the head is the wte table)."""
        cfg = self.cfg
        emb = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="wte")
        with jax.named_scope("embed"):
            x = emb(tokens)
            pos = jnp.arange(tokens.shape[-1])
            x = x + nn.Embed(
                cfg.max_len, cfg.d_model, dtype=cfg.dtype, name="wpe"
            )(pos)
            if cfg.type_vocab_size and token_types is not None:
                x = x + nn.Embed(
                    cfg.type_vocab_size, cfg.d_model, dtype=cfg.dtype,
                    name="wtt",
                )(token_types)
        block = remat_module(Block, cfg.remat)
        for i in range(cfg.n_layers):
            x = block(cfg, attention_fn=self.attention_fn, name=f"block_{i}")(
                x, mask
            )
            # int8 activation-storage boundary (identity unless an
            # act-quant trace is active — see ops/actquant.boundary).
            x = _actquant.boundary(x)
        with jax.named_scope("norm"):
            x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        if self.lm_head and not return_hidden:
            with jax.named_scope("head"):
                return emb.attend(x).astype(jnp.float32)
        return x


def rotary_tables(s: int, d: int, *, theta: float):
    """``(cos, sin)`` of ``pos * theta^(-2i/d)``, float32 numpy ``[s, d/2]``
    (positions 0 on): what :func:`rotary` multiplies by, and what the flash
    kernels take to rotate q themselves
    (``ops/pallas_kernels.QRotary``)."""
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    return (np.cos(angle).astype(np.float32),
            np.sin(angle).astype(np.float32))


def rotary(x, *, theta: float, halves: bool = False):
    """Rotates pairs of the last axis by ``pos * theta^(-2i/d)``: adjacent
    pairs ``(x[2i], x[2i+1])``, or with ``halves`` the pairs ``(x[i],
    x[i + d/2])`` (the ``rotate_half`` convention of the published
    decoder-only code; the two give the same scores up to one fixed
    permutation of a head's columns in q and k alike).  ``x`` is ``[B, S,
    ..., d]``, positions 0 on.  Computed in fp32, returned in ``x``'s
    dtype.  Shared by ``latent_moe.py`` and ``window_moe.py``."""
    d, s = x.shape[-1], x.shape[1]
    shape = (1, s) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = (jnp.asarray(t).reshape(shape)
                for t in rotary_tables(s, d, theta=theta))
    if halves:
        x32 = x.astype(jnp.float32)
        a, b = x32[..., :d // 2], x32[..., d // 2:]
        return jnp.concatenate(
            [a * cos - b * sin, a * sin + b * cos], axis=-1
        ).astype(x.dtype)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def lm_loss(logits, mtp_logits, tokens, *, mtp_weight: float):
    """``CE(logits, t_{i+1}) + mtp_weight CE(mtp_logits, t_{i+2})``, each a
    mean over every position; ``tokens [B, S + 1 + n_mtp]``: what the
    model took and one more, the last target.  Without the multi-token
    module (``mtp_logits`` None) it is the plain next-token loss.  Shared
    by ``latent_moe.py`` and ``window_moe.py``."""
    s = logits.shape[1]
    cross_entropy = optax.softmax_cross_entropy_with_integer_labels
    loss = cross_entropy(logits, tokens[:, 1:s + 1]).mean()
    if mtp_logits is not None:
        loss = loss + mtp_weight * cross_entropy(
            mtp_logits, tokens[:, 2:s + 2]
        ).mean()
    return loss
