"""Kimi Delta Attention layers beside a NoPE latent-attention layer, routed
experts held by share: the Kimi-Linear layer as a causal language model.

One model for every configuration of that family (the benchmark's
configuration file gives the published sizes); "supported" means training,
on the normal path (``dp.make_train_step`` on :func:`lm_loss`), of ONE
CHIP'S SHARE of a layer that expert parallelism divides over several, as
``latent_moe.py`` has it: this chip holds ``n_experts_held`` of each
layer's ``n_experts`` routed experts (``first_expert`` on) and a slice of
the vocabulary, routes over all experts and computes the part of the result
that its experts give (``parallel/ep.local_experts``). Serving (a recurrent
state beside a latent cache) is not built.

Equations (no bias anywhere, ``eps`` 1e-5). Pre-norm blocks::

    h' = h + mixer(RMSNorm(h));  out = h' + ffn(RMSNorm(h'))

Which mixer a layer has is DATA: ``kda_layers`` / ``full_attn_layers`` are
the published 1-indexed lists. A KDA layer (``H`` heads of ``d`` key and
value channels, ``u = RMSNorm(h)``)::

    q~, k~, v~ = u W_q, u W_k, u W_v                       [S, H d] each
    q^, k^, v^ = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))
        conv: depthwise, causal, ``conv_size`` taps, no bias:
        y_t,c = sum_i w_i,c x_{t - (conv_size - 1) + i, c}, zeros before 0
    q_t = q^_t / ||q^_t|| d^-1/2,  k_t = k^_t / ||k^_t||   per head
    g_t = -exp(A_log_h) softplus((u W_fa) W_fb + dt_bias)  [H, d] fp32 <= 0
    beta_t = sigmoid(u W_b)                                [H] fp32
    S' = Diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                                        S_0 = 0, fp32
    z_t = RMSNorm_d(o_t) sigmoid((u W_ga) W_gb)            per head
    mixer(h) = concat_heads(z) W_o

The recurrence (and the L2 norms in front of it) is
``ops/kda_kernels.kda_attention``: the chunked kernels on the TPU, the
recurrence a position at a time elsewhere. On the kernel path the
convolutions and SiLU are the kernels' too (``conv=``: the mixer hands
over its taps and ``q~ k~ v~``, and ``q^ k^ v^`` exist in VMEM only); on
the recurrence path :func:`conv_silu` runs in front of it, the one
definition of that mathematics outside the kernels. On the kernel path
the statistic of ``RMSNorm_d`` is the kernels' as well (``out_norm=eps``:
a head's output is normalised in VMEM where its tile lies, and ``o^ = o /
rms`` is what leaves them), so ``z = o^ tile(o_norm) sigmoid(gate)`` is
elementwise on ``[S, H d]`` and nothing is reshaped to heads; on the
recurrence path the gated norm is written out on ``[S, H, d]``, the one
definition outside the kernels. The latent layer is
``latent_moe.LatentAttention`` with ``q_lora_rank=None`` and
``use_rope=False`` (one ``q`` projection, NOTHING rotated, the shared
``k_r`` to the flash kernels as ``kv_a`` leaves it); the expert layer is
``latent_moe.RoutedExperts`` as it stands (sigmoid scores over all
experts, top-k of ``s + b``, weights renormalised times ``routed_scale``,
one shared expert); the first ``n_dense_layers`` layers have a dense SwiGLU
FFN. Output: final RMSNorm, an untied head over the vocabulary slice,
logits in fp32, next-token cross entropy (``transformer.lm_loss``).

What the backward keeps of a KDA layer: the three projections' outputs
``q~ k~ v~`` (the kernels' residual: the backward kernel convolves,
gates and normalises a block in VMEM again; on the recurrence path
``conv_silu``, whose backward is written out, runs again from them and
its outputs ``q^ k^ v^`` are kept as well), the taps, ``g``, ``beta``, the
chunks' entry states, ``o`` (on the kernel path ``o^`` and ``1 / rms``
a row and head in its place) and the two low-rank gate inputs (``g`` and
the gated norm run again from those).

Scopes (``jax.named_scope``; ``docs/api.md`` has the table). Every
operation of ``apply`` lies under exactly one of: ``embed``, ``norm``,
``kda_proj`` (the q / k / v / o projections, both low-rank gate pairs,
beta's), ``kda_conv`` (the three convolutions and SiLU on the recurrence
path; on the kernel path the sum and layout of the taps' partial
gradients, which the kernels' entry opens itself), ``kda_gate``
(softplus and the decay's scale, beta's sigmoid, the gated head-wise
RMSNorm: on the kernel path its scale and gate, the statistic being the
kernels'), ``mla_proj``, ``attn_layout`` (the kernels' entries' own glue),
``attn_xla`` (latent attention where flash is bypassed), ``mlp``,
``moe_route``, ``moe_experts``, ``head``. The Mosaic kernels carry none of
them; off the TPU the recurrence's ``lax.scan`` stands under
``attn_xla``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..context import device_platform
from ..ops.kda_kernels import KdaConv, kda_attention
from .latent_moe import LatentAttention, RoutedExperts
from .transformer import GatedMlp, RMSNorm, lm_loss  # noqa: F401  (lm_loss)


@dataclasses.dataclass(frozen=True)
class LinearMoEConfig:
    vocab_size: int = 163840  # rows of the vocabulary held here
    d_model: int = 2304
    n_layers: int = 27
    n_dense_layers: int = 1  # leading blocks with a dense FFN
    # which mixer a layer has, 1-indexed as published
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_heads: int = 32
    kda_head_dim: int = 128  # key and value channels alike
    conv_size: int = 4
    gate_rank: int = 128  # of the two low-rank gate pairs
    # the latent layers (fields as latent_moe.LatentAttention reads them)
    n_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    use_rope: bool = False  # NoPE: nothing reads the published rope_theta
    # the FFNs (fields as latent_moe.RoutedExperts reads them)
    d_ff_dense: int = 9216
    d_ff_expert: int = 1024
    n_experts: int = 256  # the router's width
    n_experts_held: int = 256  # routed experts whose weights live here
    first_expert: int = 0  # ... and the first of them
    top_k: int = 8
    routed_scale: float = 2.446
    n_shared_experts: int = 1
    eps: float = 1e-5
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    # None: the flash kernels where the world's devices are TPUs
    use_flash: Optional[bool] = None
    # None: the KDA kernels where the world's devices are TPUs
    use_kernel: Optional[bool] = None

    def mixer(self, layer: int) -> str:
        """``"kda"`` or ``"latent"`` for 0-indexed ``layer``."""
        if layer + 1 in self.kda_layers:
            return "kda"
        if layer + 1 in self.full_attn_layers:
            return "latent"
        raise ValueError(f"layer {layer + 1} is in neither layout list")

    @staticmethod
    def tiny(**kw) -> "LinearMoEConfig":
        base = dict(
            vocab_size=256, d_model=64, n_layers=5, kda_layers=(1, 2, 3, 5),
            full_attn_layers=(4,), kda_heads=2, kda_head_dim=16,
            gate_rank=16, n_heads=2, kv_lora_rank=16, qk_nope_dim=16,
            qk_rope_dim=8, v_dim=16, d_ff_dense=96, d_ff_expert=24,
            n_experts=32, n_experts_held=8, top_k=4,
        )
        base.update(kw)
        return LinearMoEConfig(**base)


def _init(cfg: LinearMoEConfig):
    return nn.initializers.normal(cfg.init_std)


def _decay_rates(key, shape, dtype=jnp.float32):
    """``A_log = log U(1, 16)``, one a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _step_bias(key, shape, dtype=jnp.float32):
    """``dt_bias = softplus^-1(dt)``, ``dt`` log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def _taps(key, shape, dtype=jnp.float32):
    """A depthwise convolution's taps, U(-0.5, 0.5)."""
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


def _behind(x, k: int):
    """Row ``t`` of the result is row ``t - k`` of ``x [B, S, W]``, zeros
    before row 0."""
    return x if k == 0 else jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, :x.shape[1]]


def _ahead(x, k: int):
    """Row ``t`` of the result is row ``t + k`` of ``x``, zeros past the end."""
    return x if k == 0 else jnp.pad(x, ((0, 0), (0, k), (0, 0)))[:, k:]


def _conv(x, taps):
    n = taps.shape[0]
    x = x.astype(jnp.float32)
    return sum(taps[i] * _behind(x, n - 1 - i) for i in range(n))


@jax.custom_vjp
def conv_silu(x, taps):
    """``SiLU`` of the depthwise causal convolution of ``x [B, S, W]`` with
    ``taps [n, W]`` (tap ``n - 1`` meets the position itself), written as
    ``n`` shifted multiplies in float32. The backward is written out: it
    keeps ``x`` alone, runs the convolution again, and hands ``dx`` back as
    the same ``n`` shifted multiplies of ONE array (the gradient before the
    SiLU, in ``x``'s dtype), where autodiff's transpose of the shifted
    slices made ``n`` float32 arrays of ``x``'s shape a convolution."""
    return jax.nn.silu(_conv(x, taps)).astype(x.dtype)


def _conv_silu_fwd(x, taps):
    return conv_silu(x, taps), (x, taps)


def _conv_silu_bwd(kept, dy):
    x, taps = kept
    n = taps.shape[0]
    # tied to the cotangent, as jax.checkpoint ties what it runs again: the
    # compiler would otherwise keep the forward's float32 convolution
    # (twice x's bytes, twelve times a step) in place of running it again
    x, dy = jax.lax.optimization_barrier((x, dy))
    c = _conv(x, taps)
    gate = jax.nn.sigmoid(c)
    d_pre = (dy.astype(jnp.float32) * (gate * (1.0 + c * (1.0 - gate)))).astype(
        x.dtype
    ).astype(jnp.float32)
    dx = sum(taps[i] * _ahead(d_pre, n - 1 - i) for i in range(n))
    x = x.astype(jnp.float32)
    d_taps = jnp.stack([
        jnp.sum(d_pre * _behind(x, n - 1 - i), axis=(0, 1)) for i in range(n)
    ])
    return dx.astype(kept[0].dtype), d_taps


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def _to(dtype, x, w):
    """``x w`` on ``dtype`` operands, summed and handed back in float32."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


class KimiDeltaAttention(nn.Module):
    """The KDA mixer: projections, convolutions, the two low-rank gates
    and the gated head-wise RMSNorm around ``kda_attention`` (which on the
    kernel path convolves at its door and normalises at its exit)."""

    cfg: LinearMoEConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        b, s, _ = u.shape
        h, d, rank = cfg.kda_heads, cfg.kda_head_dim, cfg.gate_rank
        width = h * d
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, name=name,
            kernel_init=_init(cfg),
        )
        matrix = lambda name, shape: self.param(  # noqa: E731
            name, _init(cfg), shape, jnp.float32
        )
        taps = KdaConv(*(
            self.param(f"conv_{x}", _taps, (cfg.conv_size, width), jnp.float32)
            for x in "qkv"
        ))
        f_b, g_b = matrix("f_b", (rank, width)), matrix("g_b", (rank, width))
        w_beta = matrix("b", (cfg.d_model, h))
        a_log = self.param("A_log", _decay_rates, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _step_bias, (width,), jnp.float32)
        out_scale = self.param(
            "o_norm", nn.initializers.ones, (d,), jnp.float32
        )

        use_kernel = cfg.use_kernel
        if use_kernel is None:
            use_kernel = device_platform() == "tpu"
        with jax.named_scope("kda_proj"):
            q, k, v = (dense(width, x)(u) for x in "qkv")
            f_a, g_a = dense(rank, "f_a")(u), dense(rank, "g_a")(u)
            beta_logits = _to(cfg.dtype, u, w_beta)
        if not use_kernel:  # the kernels convolve at their door, in VMEM
            with jax.named_scope("kda_conv"):
                q, k, v = (conv_silu(x, w) for x, w in zip((q, k, v), taps))

        @jax.checkpoint
        def log_decay(f_a, f_b, dt_bias, a_log):
            with jax.named_scope("kda_proj"):
                pre = _to(cfg.dtype, f_a, f_b)
            with jax.named_scope("kda_gate"):
                rate = jnp.repeat(-jnp.exp(a_log), d)  # a head's, per channel
                return rate * jax.nn.softplus(pre + dt_bias)

        g = log_decay(f_a, f_b, dt_bias, a_log)
        with jax.named_scope("kda_gate"):
            beta = jax.nn.sigmoid(beta_logits)
        if use_kernel:  # o^: each head's output normalised at the exit
            o = kda_attention(q, k, v, g, beta, n_heads=h, conv=taps,
                              out_norm=cfg.eps, use_kernel=True)
        else:
            with jax.named_scope("attn_xla"):
                o = kda_attention(q, k, v, g, beta, n_heads=h,
                                  use_kernel=False)

        @jax.checkpoint
        def gated_norm(o, g_a, g_b, out_scale):
            with jax.named_scope("kda_proj"):
                gate = _to(cfg.dtype, g_a, g_b)
            with jax.named_scope("kda_gate"):
                if use_kernel:  # normalised already: no reshape to heads
                    normed = o.astype(jnp.float32) * jnp.tile(out_scale, h)
                else:
                    heads = o.astype(jnp.float32).reshape(b, s, h, d)
                    heads = heads * jax.lax.rsqrt(
                        jnp.mean(heads * heads, axis=-1, keepdims=True)
                        + cfg.eps
                    ) * out_scale
                    normed = heads.reshape(b, s, width)
                return (normed * jax.nn.sigmoid(gate)).astype(cfg.dtype)

        z = gated_norm(o, g_a, g_b, out_scale)
        with jax.named_scope("kda_proj"):
            return dense(cfg.d_model, "o")(z)


class LinearMoEBlock(nn.Module):
    cfg: LinearMoEConfig
    mixer: str = "kda"  # or "latent"
    dense_ffn: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.eps, cfg.dtype, name=name)  # noqa: E731
        with jax.named_scope("norm"):
            h = norm("attn_norm")(x)
        attend = KimiDeltaAttention if self.mixer == "kda" else LatentAttention
        h = attend(cfg, name="attn")(h)
        with jax.named_scope("norm"):
            x = x + h
            h = norm("ffn_norm")(x)
        if self.dense_ffn:
            h = GatedMlp(cfg.d_ff_dense, cfg.dtype, _init(cfg), name="ffn")(h)
        else:
            h = RoutedExperts(cfg, name="ffn")(h)
        with jax.named_scope("norm"):
            return x + h


class LinearMoELM(nn.Module):
    """``tokens [B, S] -> logits`` fp32 ``[B, S, vocab]``; ``logits[:, i]``
    predicts the token after ``tokens[:, i]``."""

    cfg: LinearMoEConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        head = self.param(
            "head", _init(cfg), (cfg.d_model, cfg.vocab_size), jnp.float32
        )
        with jax.named_scope("embed"):
            x = nn.Embed(
                cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed",
                embedding_init=_init(cfg),
            )(tokens)
        for i in range(cfg.n_layers):
            x = LinearMoEBlock(
                cfg, mixer=cfg.mixer(i), dense_ffn=i < cfg.n_dense_layers,
                name=f"block_{i}",
            )(x)
        with jax.named_scope("norm"):
            x = RMSNorm(cfg.eps, cfg.dtype, name="final_norm")(x)
        with jax.named_scope("head"):
            return jnp.dot(
                x, head.astype(cfg.dtype), preferred_element_type=jnp.float32
            )
