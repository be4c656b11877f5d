"""Gated DeltaNet layers beside full-attention layers under a dense SwiGLU
FFN in every block, the mixers' heads held by share: the Olmo-Hybrid layer
as a causal language model.

One model for every configuration of that family (the benchmark's
configuration file gives the published sizes); "supported" means training,
on the normal path (``dp.make_train_step`` on :func:`lm_loss`), of ONE
CHIP'S SHARE of a layer whose mixer is divided over several chips BY HEADS:
this chip holds ``heads_held`` of each mixer's ``n_heads`` heads
(``first_head`` on), the FFN whole and a slice of the vocabulary. Serving
(a recurrent state beside a K/V cache) is not built.

Equations (no bias anywhere). Blocks norm each sub-layer's OUTPUT, before
the residual add (the Olmo-2 / Olmo-3 convention)::

    h' = h + RMSNorm(mixer(h));  out = h' + RMSNorm(ffn(h'))

Which mixer a layer has is DATA: ``layer_types`` is the published list
(``"linear_attention"`` / ``"full_attention"``). A Gated DeltaNet layer
(``H`` heads held, ``d_k`` key and ``d_v`` value channels a head, input
``u``)::

    q~, k~ = u W_q, u W_k            [S, H d_k];   v~, z = u W_v, u W_z  [S, H d_v]
    q, k, v = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))
        conv: depthwise, causal, ``conv_size`` taps, no bias, zeros before 0
    q^ = q / ||q|| d_k^-1/2,  k^ = k / ||k||                       per head
    g_t = -exp(A_log_h) softplus(u_t w_a,h + dt_bias_h)   [H] fp32 <= 0, ONE a head
    beta_t = 2 sigmoid(u_t w_b,h)   [H] fp32 (the 2: ``allow_neg_eigval``)
    S_t = exp(g_t) S_{t-1} (I - beta_t k^_t k^_t^T) + beta_t v_t k^_t^T
    o_t = S_t q^_t                                   S in R^{d_v x d_k}, S_0 = 0
    mixer(u) = concat_heads(RMSNorm_dv(o) * SiLU(z)) W_o

The recurrence (and the L2 norms in front of it) is
``ops/kda_kernels.kda_attention`` with ``g`` ``[B, S, H]``: the
scalar-gate chunked kernels (``hvd_gdn_fwd`` / ``hvd_gdn_bwd``) on the TPU,
the recurrence a position at a time elsewhere. On the kernel path the
convolutions and SiLU are the kernels' (``conv=``) and so is the statistic
of ``RMSNorm_dv`` (``out_norm=eps``: ``o / rms`` leaves them), as in
``linear_moe.KimiDeltaAttention``; on the recurrence path
``linear_moe.conv_silu`` runs in front and the gated norm is written out.

A full-attention layer is ``window_moe.GroupedAttention`` with as many K/V
heads as query heads, no window, NOTHING rotated (the recurrent layers
carry position), and q and k RMS-normalised over their WHOLE projection
(``qk_norm_over="projection"``), not a head.

**The share.** ``axis_name`` names a mesh axis over which the chips that
share a layer's heads are laid: the two sums such a layer exchanges, the
projection norm's sum of squares and the output projection's partial
result (of both kinds of mixer), are ``psum``med over it. With no axis
(the benchmark's cell) nothing is exchanged, the norm's statistic is over
the columns held, and what the absent heads would add is absent. The FFN is
whole on every chip. No code stands in for another chip.

Output: final RMSNorm, an untied head over the vocabulary slice, logits in
fp32, next-token cross entropy (``transformer.lm_loss``).

Scopes (``jax.named_scope``; ``docs/api.md`` has the table). Every
operation of ``apply`` lies under exactly one of: ``embed``, ``norm``,
``gdn_proj`` (the q / k / v / z / o projections, ``a``'s and ``b``'s),
``gdn_gate`` (softplus and the decay's scale, beta's sigmoid, ``norm *
SiLU(z)``), ``kda_conv`` (the convolutions and SiLU on the recurrence
path; on the kernel path the taps' partial gradients summed, which the
kernels' entry opens itself), ``attn_proj``, ``attn_layout``, ``attn_xla``
(attention where flash is bypassed, and the recurrence's ``lax.scan``),
``mlp``, ``head``. The Mosaic kernels carry none of them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..context import device_platform
from ..ops.kda_kernels import KdaConv, kda_attention
from .linear_moe import _decay_rates, _step_bias, _taps, _to, conv_silu
from .transformer import GatedMlp, RMSNorm, lm_loss  # noqa: F401  (lm_loss)
from .window_moe import GroupedAttention, WindowMoEConfig

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class LinearDenseConfig:
    vocab_size: int = 100352  # rows of the vocabulary held here
    d_model: int = 3840
    n_layers: int = 32
    # which mixer a layer has, as published; a layer reads its own entry
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    n_heads: int = 30  # of every mixer, as the model is parametrised
    heads_held: int = 30  # ... of which this chip holds these many
    first_head: int = 0  # ... from this one on
    head_dim: int = 128  # full attention
    gdn_key_dim: int = 96  # a Gated DeltaNet head's key channels
    gdn_value_dim: int = 192  # ... and value channels
    conv_size: int = 4
    neg_eigval: bool = True  # beta in (0, 2)
    d_ff: int = 11008
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    # the mesh axis the chips that share a layer's heads lie along
    axis_name: Optional[str] = None
    # None: the flash kernels where the world's devices are TPUs
    use_flash: Optional[bool] = None
    # None: the scalar-gate kernels where the world's devices are TPUs
    use_kernel: Optional[bool] = None

    def mixer(self, layer: int) -> str:
        kind = self.layer_types[layer]
        if kind not in (LINEAR, FULL):
            raise ValueError(f"layer {layer}: layer_types has {kind!r}")
        return kind

    def attention(self) -> WindowMoEConfig:
        """What ``window_moe.GroupedAttention`` reads: the heads HELD, each
        on its own K/V head, q and k normed, no indexer."""
        return WindowMoEConfig(
            d_model=self.d_model, n_heads=self.heads_held,
            n_kv_heads=self.heads_held, head_dim=self.head_dim,
            eps=self.eps, init_std=self.init_std, dtype=self.dtype,
            use_flash=self.use_flash, qk_norm=True,
        )

    @staticmethod
    def tiny(**kw) -> "LinearDenseConfig":
        base = dict(
            vocab_size=256, d_model=48, n_layers=4,
            layer_types=(LINEAR, LINEAR, LINEAR, FULL), n_heads=4,
            heads_held=2, head_dim=16, gdn_key_dim=12, gdn_value_dim=24,
            d_ff=80,
        )
        base.update(kw)
        return LinearDenseConfig(**base)


def _init(cfg: LinearDenseConfig):
    return nn.initializers.normal(cfg.init_std)


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet mixer over the heads this chip holds:
    projections, the two gates and the gated head-wise RMSNorm around
    ``kda_attention`` in its scalar-gate form (which on the kernel path
    convolves at its door and normalises at its exit)."""

    cfg: LinearDenseConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, s, _ = u.shape
        h, dk, dv = cfg.heads_held, cfg.gdn_key_dim, cfg.gdn_value_dim
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, name=name,
            kernel_init=_init(cfg),
        )
        matrix = lambda name: self.param(  # noqa: E731
            name, _init(cfg), (cfg.d_model, h), jnp.float32
        )
        taps = KdaConv(*(
            self.param(f"conv_{x}", _taps, (cfg.conv_size, h * d),
                       jnp.float32)
            for x, d in zip("qkv", (dk, dk, dv))
        ))
        w_a, w_b = matrix("a"), matrix("b")
        a_log = self.param("A_log", _decay_rates, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _step_bias, (h,), jnp.float32)
        out_scale = self.param(
            "o_norm", nn.initializers.ones, (dv,), jnp.float32
        )

        use_kernel = cfg.use_kernel
        if use_kernel is None:
            use_kernel = device_platform() == "tpu"
        with jax.named_scope("gdn_proj"):
            q, k = dense(h * dk, "q")(u), dense(h * dk, "k")(u)
            v, z = dense(h * dv, "v")(u), dense(h * dv, "z")(u)
            rate_logits = _to(cfg.dtype, u, w_a)
            beta_logits = _to(cfg.dtype, u, w_b)
        if not use_kernel:  # the kernels convolve at their door, in VMEM
            with jax.named_scope("kda_conv"):
                q, k, v = (conv_silu(x, w) for x, w in zip((q, k, v), taps))
        with jax.named_scope("gdn_gate"):
            g = -jnp.exp(a_log) * jax.nn.softplus(rate_logits + dt_bias)
            beta = jax.nn.sigmoid(beta_logits)
            if cfg.neg_eigval:
                beta = 2.0 * beta
        if use_kernel:  # o^: each head's output normalised at the exit
            o = kda_attention(q, k, v, g, beta, n_heads=h, conv=taps,
                              out_norm=cfg.eps, use_kernel=True)
        else:
            with jax.named_scope("attn_xla"):
                o = kda_attention(q, k, v, g, beta, n_heads=h,
                                  use_kernel=False)

        @jax.checkpoint
        def gated_norm(o, z, out_scale):
            with jax.named_scope("gdn_gate"):
                if use_kernel:  # normalised already: no reshape to heads
                    normed = o.astype(jnp.float32) * jnp.tile(out_scale, h)
                else:
                    heads = o.astype(jnp.float32).reshape(b, s, h, dv)
                    heads = heads * jax.lax.rsqrt(
                        jnp.mean(heads * heads, axis=-1, keepdims=True)
                        + cfg.eps
                    ) * out_scale
                    normed = heads.reshape(b, s, h * dv)
                return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(
                    cfg.dtype
                )

        y = gated_norm(o, z, out_scale)
        with jax.named_scope("gdn_proj"):
            out = dense(cfg.d_model, "o")(y)
            if cfg.axis_name is not None:  # the other shares' heads
                out = jax.lax.psum(out, cfg.axis_name)
            return out


class LinearDenseBlock(nn.Module):
    cfg: LinearDenseConfig
    mixer: str = LINEAR

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.eps, cfg.dtype, name=name)  # noqa: E731
        if self.mixer == LINEAR:
            h = GatedDeltaNet(cfg, name="attn")(x)
        else:
            h = GroupedAttention(
                cfg.attention(), qk_norm_over="projection",
                axis_name=cfg.axis_name, name="attn",
            )(x)
        with jax.named_scope("norm"):
            x = x + norm("attn_norm")(h)
        h = GatedMlp(cfg.d_ff, cfg.dtype, _init(cfg), name="ffn")(x)
        with jax.named_scope("norm"):
            return x + norm("ffn_norm")(h)


class LinearDenseLM(nn.Module):
    """``tokens [B, S] -> logits`` fp32 ``[B, S, vocab]``; ``logits[:, i]``
    predicts the token after ``tokens[:, i]``."""

    cfg: LinearDenseConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        if not 0 <= cfg.first_head <= cfg.n_heads - cfg.heads_held:
            raise ValueError(
                f"heads {cfg.first_head} to {cfg.first_head + cfg.heads_held}"
                f" of {cfg.n_heads}"
            )
        head = self.param(
            "head", _init(cfg), (cfg.d_model, cfg.vocab_size), jnp.float32
        )
        with jax.named_scope("embed"):
            x = nn.Embed(
                cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed",
                embedding_init=_init(cfg),
            )(tokens)
        for i in range(cfg.n_layers):
            x = LinearDenseBlock(
                cfg, mixer=cfg.mixer(i), name=f"block_{i}"
            )(x)
        with jax.named_scope("norm"):
            x = RMSNorm(cfg.eps, cfg.dtype, name="final_norm")(x)
        with jax.named_scope("head"):
            return jnp.dot(
                x, head.astype(cfg.dtype), preferred_element_type=jnp.float32
            )
