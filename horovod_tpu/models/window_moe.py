"""Window and full attention layers mixed, query heads in groups, routed
experts held by share, the router ahead of the attention: the SmallThinker
layer as a causal language model.

One model for every configuration of that family (the benchmark's
configuration file gives the published sizes); "supported" means training,
on the normal path (``dp.make_train_step`` on :func:`lm_loss`), of ONE
CHIP'S SHARE of a layer that expert parallelism divides over several, as
``latent_moe.py`` has it: this chip holds ``n_experts_held`` of each
layer's ``n_experts`` routed experts (``first_expert`` on) and a slice of
the vocabulary, routes over all experts and computes the part of the result
that its experts give (``parallel/ep.local_experts``). Serving (a cache of
two kinds of layer) is not built.

Equations (no bias anywhere, ``eps`` 1e-6). Layer ``l`` with input ``h``
``[S, d_model]``, ``H`` query heads and ``H_kv`` K/V heads of ``head_dim``::

    u = RMSNorm1(h)
    r = u W_r  in fp32 over all E;  C = the k largest of r
    w = softmax(r[C])          (= softmax over E renormalised over C)
    q = u W_q -> H x head_dim;  k = u W_k, v = u W_v -> H_kv x head_dim
    rope_layout[l] = 1:    q, k <- rotary(theta, halves, no scaling)
    window_layout[l] = 1:  valid(i, j): 0 <= i - j < window
                     = 0:  valid(i, j): j <= i
    a = softmax_j(q_i k_j / sqrt(head_dim)) v_j;  query head n reads K/V
        head n // (H / H_kv)
    h' = h + concat(a) W_o
    m = RMSNorm2(h')
    out = h' + sum_{e in C, held} w_e W_down,e (relu(W_gate,e m) * W_up,e m)

The router reads the ATTENTION's input, so the experts of a layer are
chosen before its attention runs; the experts themselves read
``RMSNorm2(h')``. Every layer is an expert layer: no shared expert, no
dense layer.

Static settings of the same block, each at the SmallThinker layer's value
by default (a default configuration traces what it traced):
``router_input`` (``"ffn_norm"``: the router reads ``m``, what the experts
read), ``expert_activation`` (``"silu"``), ``qk_norm`` (q and k are
RMS-normalised over each head's ``head_dim`` columns, one learned scale a
projection, before the rotary), and ``index_top_k`` > 0: learned sparse
attention (``ops/dsa_kernels.py`` has the equations). A lightning indexer
(``index_heads`` query heads of ``index_head_dim`` on ONE key head, reading
``stop_gradient(u)``)::

    qI = x W_qI;  kI = LayerNorm(x W_kI);  w = (x W_wI) H_I^-1/2 d_I^-1/2
    qI, kI <- rotary(theta, halves) over their d_I columns
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])   fp32, s <= t
    sel(t)  = the index_top_k largest of I[t, 0..t], ties all kept

scores every earlier position, attention runs over ``sel(t)`` alone (a
constant of the step: the flash kernels under ``keep=``), and the layer
adds ``L_I = mean_t KL(stop_gradient(mean_n p_n[t]) || softmax_sel(I[t]))``
to an index loss that ``apply`` then returns beside the logits, ``(logits,
index_loss)``: the training loss is ``lm_loss(logits, ...) + index_loss``.
The indexer's leaves learn from ``L_I`` alone and every other leaf from the
cross entropy alone. Output: final RMSNorm, an untied head over the vocabulary
slice, logits in fp32, next-token cross entropy (``transformer.lm_loss``).

The two layouts are tuples a layer indexes modulo their length, so a
period (``(0, 1, 1, 1)``: full, window, window, window) is data. The flash
kernels take q ``[B, S, H * head_dim]`` and k / v ``[B, S, H_kv *
head_dim]`` as the projections leave them (``layout="bsm"``,
``n_kv_heads``, ``window``): no K or V of ``H`` heads exists, and q goes in
as its projection leaves it: with ``qk_norm`` unnormed (``q_norm``: the
scale is the ``q_norm/scale`` leaf), in a rotated layer unrotated
(``q_rotary``); the kernels norm and rotate it in VMEM, hand back the
gradient of the projection's output and the scale's, and give the index
loss the q their scores saw (``return_q``); k is normed and rotated here. A window
layer's kernels are named ``hvd_flash_*_window``, a full layer's
``hvd_flash_*``. Off the TPU attention is ``dot_product_attention`` with
the explicit band mask over K/V repeated to ``H`` heads.

``GroupedAttention`` has two settings of its own for a model that is not
this block (``models/linear_dense.py``): ``qk_norm_over`` and
``axis_name``, on the class; their defaults trace what the layer traced.

Scopes (``jax.named_scope``; ``docs/api.md`` has the table). Every
operation of ``apply`` lies under exactly one of: ``embed``, ``norm`` (the
RMSNorms, the residual sums and the token-major views), ``attn_proj`` (the
four projections, k's head-wise norm and rotary; off the flash path q's
too), ``index_proj`` (the indexer's three
projections, its LayerNorm and rotary, and the select kernels' own glue),
``attn_layout`` (the reshapes between the
projections and the kernels, and the kernels' entry's own glue),
``attn_xla`` (attention where flash is bypassed), ``moe_route``,
``moe_experts`` (``parallel/ep.py``), ``head``. There is no ``mlp`` part:
the model has no dense feed-forward. The Mosaic kernels carry none of them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..context import device_platform
from ..parallel import ep
from .transformer import (  # noqa: F401  (lm_loss: the model's loss)
    RMSNorm, dot_product_attention, lm_loss, rotary, rotary_tables,
)


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab_size: int = 151936  # rows of the vocabulary held here
    d_model: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    window: int = 4096
    # per layer, indexed modulo the length: 1 = window / rotary
    window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_theta: float = 1.5e6
    d_ff_expert: int = 768
    n_experts: int = 64  # the router's width
    n_experts_held: int = 64  # routed experts whose weights live here
    first_expert: int = 0  # ... and the first of them
    top_k: int = 6
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    # None: the flash kernels where the world's devices are TPUs
    use_flash: Optional[bool] = None
    # "attn_norm": the router reads RMSNorm1(h), ahead of the attention;
    # "ffn_norm": RMSNorm2(h'), what the experts read
    router_input: str = "attn_norm"
    expert_activation: str = "relu"  # or "silu"
    qk_norm: bool = False  # RMSNorm over each head of q and k
    # > 0: a lightning indexer keeps each query's index_top_k best keys
    index_top_k: int = 0
    index_heads: int = 16
    index_head_dim: int = 64
    # (queries a select program takes, the key tile it scores them against)
    index_blocks: Tuple[int, int] = (128, 512)

    def windowed(self, layer: int) -> bool:
        return bool(self.window_layout[layer % len(self.window_layout)])

    def rotated(self, layer: int) -> bool:
        return bool(self.rope_layout[layer % len(self.rope_layout)])

    @staticmethod
    def tiny(**kw) -> "WindowMoEConfig":
        base = dict(
            vocab_size=256, d_model=48, n_layers=4, n_heads=6, n_kv_heads=2,
            head_dim=16, window=8, d_ff_expert=24, n_experts=16,
            n_experts_held=4, top_k=3,
        )
        base.update(kw)
        return WindowMoEConfig(**base)


def band_mask(s: int, window: Optional[int]):
    """``[s, s]`` bool: ``valid(i, j) = 0 <= i - j (< window)``."""
    ahead = np.arange(s)[:, None] - np.arange(s)[None, :]
    valid = ahead >= 0
    return valid if window is None else valid & (ahead < window)


def _init(cfg: WindowMoEConfig):
    return nn.initializers.normal(cfg.init_std)


class HeadScale(nn.Module):
    """An ``RMSNorm``'s parameter alone, ``scale`` ``[d]`` float32 at one,
    under the norm's name: where the flash kernels norm q themselves the
    model holds the scale and forms nothing with it."""

    @nn.compact
    def __call__(self, d: int):
        return self.param("scale", nn.initializers.ones, (d,), jnp.float32)


class ProjectionNorm(nn.Module):
    """``RMSNorm`` over a WHOLE projection's columns (``scale`` ``[width]``
    float32 at one), of which this chip may hold a share: under
    ``axis_name`` the sum of squares is ``psum``med over the shares and the
    mean is over all their columns; with none it is over the columns held.
    fp32, returned in ``dtype``."""

    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        width = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (width,),
                           jnp.float32)
        x = x.astype(jnp.float32)
        squares = jnp.sum(x * x, axis=-1, keepdims=True)
        if self.axis_name is not None:
            squares = jax.lax.psum(squares, self.axis_name)
            width = width * jax.lax.psum(1, self.axis_name)
        y = x * jax.lax.rsqrt(squares / width + self.eps)
        return (y * scale).astype(self.dtype)


class GroupedAttention(nn.Module):
    """Causal attention with ``n_heads`` query heads over ``n_kv_heads``
    K/V heads, under a ``window`` (None: every earlier position) and with
    or without rotary. With ``cfg.index_top_k`` each query attends to the
    keys its indexer keeps, and the call returns ``(out, index_loss)``.

    ``qk_norm_over`` (read where ``cfg.qk_norm`` is on): ``"head"`` norms
    each head of q and k over its ``head_dim`` columns, ``"projection"``
    each WHOLE projection over all its columns (:class:`ProjectionNorm`;
    XLA's on both paths, since the kernels' ``q_norm`` is a head's).
    ``axis_name``: the layer's heads are one share of several under that
    axis; the projection norm's sum of squares and the output projection's
    partial result are ``psum``med over it. None: nothing is exchanged."""

    cfg: WindowMoEConfig
    window: Optional[int] = None
    rotate: bool = False
    qk_norm_over: str = "head"
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, h_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=cfg.dtype, name=name,
            kernel_init=_init(cfg),
        )
        use_flash = cfg.use_flash
        if use_flash is None:
            use_flash = device_platform() == "tpu"
        if use_flash:
            from ..ops.pallas_kernels import (
                QNorm, QRotary, flash_attention_with_lse,
            )
        turn = lambda t, heads: rotary(  # noqa: E731
            t.reshape(b, s, heads, -1), theta=cfg.rope_theta, halves=True,
        ).reshape(b, s, -1)
        with jax.named_scope("attn_proj"):
            q = dense(h * d, "q")(x)
            k = dense(h_kv * d, "k")(x)
            v = dense(h_kv * d, "v")(x)
            q_norm = None
            if cfg.qk_norm and self.qk_norm_over == "projection":
                whole = lambda name: ProjectionNorm(  # noqa: E731
                    cfg.eps, cfg.dtype, self.axis_name, name=name
                )
                q, k = whole("q_norm")(q), whole("k_norm")(k)
            elif cfg.qk_norm:
                by_head = lambda t, heads, name: RMSNorm(  # noqa: E731
                    cfg.eps, cfg.dtype, name=name
                )(t.reshape(b, s, heads, d)).reshape(b, s, heads * d)
                if use_flash:  # the kernels norm q: they take the scale
                    q_norm = QNorm(HeadScale(name="q_norm")(d), cfg.eps)
                else:
                    q = by_head(q, h, "q_norm")
                k = by_head(k, h_kv, "k_norm")
            if self.rotate:
                k = turn(k, h_kv)
                if not use_flash:
                    q = turn(q, h)
        keep = None
        if cfg.index_top_k:
            from ..ops.dsa_kernels import dsa_index_loss, dsa_select

            q_idx, k_idx, w = self.index(x, turn)
            keep, _, lse_idx = dsa_select(
                q_idx, k_idx, w, top_k=cfg.index_top_k,
                use_kernel=use_flash, block_q=cfg.index_blocks[0],
                block_k=cfg.index_blocks[1],
            )
        lse = None
        if use_flash:
            # q, k and v as the projections leave them: no relayout, and
            # no K or V of ``h`` heads; the kernels norm and rotate q (28
            # heads) and hand back the gradient of the projection's output,
            # k (4) is normed and rotated above.  The index loss's target
            # reads the q the kernels' scores saw.
            out, lse, *q_seen = flash_attention_with_lse(
                q, k, v, causal=True, window=self.window, layout="bsm",
                n_heads=h, n_kv_heads=h_kv,
                q_rotary=QRotary(
                    *rotary_tables(s, d, theta=cfg.rope_theta), halves=True
                ) if self.rotate else None,
                keep=keep, q_norm=q_norm, return_q=bool(cfg.index_top_k),
            )
            q = q_seen[0] if q_seen else q
        else:
            with jax.named_scope("attn_xla"):
                heads = lambda t, n: t.reshape(b, s, n, d)  # noqa: E731
                shared = lambda t: jnp.repeat(  # noqa: E731
                    heads(t, h_kv), h // h_kv, axis=2
                )
                mask = jnp.asarray(band_mask(s, self.window))
                if keep is not None:  # [b, keys, queries] -> [b, 1, q, k]
                    mask = mask & (keep.swapaxes(1, 2) != 0)[:, None]
                out = dot_product_attention(
                    heads(q, h), shared(k), shared(v), causal=False,
                    mask=mask,
                )
            with jax.named_scope("attn_layout"):
                out = out.reshape(b, s, h * d)
        with jax.named_scope("attn_proj"):
            out = dense(cfg.d_model, "o")(out)
            if self.axis_name is not None:  # the other shares' heads
                out = jax.lax.psum(out, self.axis_name)
        if not cfg.index_top_k:
            return out
        return out, dsa_index_loss(
            q, k, lse, q_idx, k_idx, w, keep, lse_idx, n_heads=h,
            n_kv_heads=h_kv, use_kernel=use_flash,
        )

    def index(self, x, turn):
        """The indexer's ``(q_idx [B, S, H_I d_I], k_idx [B, S, d_I], w [B,
        S, H_I] fp32)`` from ``x``, to which they carry no gradient."""
        cfg = self.cfg
        n, width = cfg.index_heads, cfg.index_head_dim
        weigh = self.param(
            "index_w", _init(cfg), (cfg.d_model, n), jnp.float32
        )
        with jax.named_scope("index_proj"):
            x = jax.lax.stop_gradient(x)
            q_idx = nn.Dense(
                n * width, use_bias=False, dtype=cfg.dtype, name="index_q",
                kernel_init=_init(cfg),
            )(x)
            k_idx = nn.LayerNorm(
                epsilon=cfg.eps, dtype=cfg.dtype, name="index_k_norm"
            )(nn.Dense(
                width, use_bias=False, dtype=cfg.dtype, name="index_k",
                kernel_init=_init(cfg),
            )(x))
            w = jnp.dot(
                x, weigh.astype(cfg.dtype), preferred_element_type=jnp.float32
            ) * (n ** -0.5 * width ** -0.5)
            if self.rotate:
                q_idx, k_idx = turn(q_idx, n), turn(k_idx, 1)
        return q_idx, k_idx, w


class WindowMoEBlock(nn.Module):
    """One layer: the router reads ``RMSNorm1(h)`` before the attention
    does (or ``RMSNorm2(h')``: ``cfg.router_input``), the held experts read
    ``RMSNorm2(h')``. With ``cfg.index_top_k``: ``(x, index_loss)``."""

    cfg: WindowMoEConfig
    windowed: bool = False
    rotate: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        held, f = cfg.n_experts_held, cfg.d_ff_expert
        if cfg.router_input not in ("attn_norm", "ffn_norm"):
            raise ValueError(f"router_input: {cfg.router_input!r}")
        norm = lambda name: RMSNorm(cfg.eps, cfg.dtype, name=name)  # noqa: E731
        param = lambda name, shape: self.param(  # noqa: E731
            name, _init(cfg), shape, jnp.float32
        )
        router = param("router", (d, cfg.n_experts))
        gate = param("experts_gate", (held, d, f))
        up = param("experts_up", (held, d, f))
        down = param("experts_down", (held, f, d))

        def route(tokens):
            with jax.named_scope("moe_route"):
                return ep.topk_route(
                    tokens, router, None, top_k=cfg.top_k, scoring="softmax"
                )

        with jax.named_scope("norm"):
            u = norm("attn_norm")(x)
            tokens = u.reshape(b * s, d)  # free: the token-major view
        if cfg.router_input == "attn_norm":
            chosen, weights = route(tokens)
        a = GroupedAttention(
            cfg, window=cfg.window if self.windowed else None,
            rotate=self.rotate, name="attn",
        )(u)
        index_loss = None
        if cfg.index_top_k:
            a, index_loss = a
        with jax.named_scope("norm"):
            x = x + a
            tokens = norm("ffn_norm")(x).reshape(b * s, d)
        if cfg.router_input == "ffn_norm":
            chosen, weights = route(tokens)
        y = ep.local_experts(
            tokens, chosen, weights, gate, up, down,
            first_expert=cfg.first_expert, n_experts=cfg.n_experts,
            activation=cfg.expert_activation,
        )
        with jax.named_scope("norm"):
            x = x + y.reshape(b, s, d)
        return x if index_loss is None else (x, index_loss)


class WindowMoELM(nn.Module):
    """``tokens [B, S] -> logits`` fp32 ``[B, S, vocab]``; ``logits[:, i]``
    predicts the token after ``tokens[:, i]``. With ``cfg.index_top_k``:
    ``(logits, index_loss)``, the layers' ``L_I`` summed."""

    cfg: WindowMoEConfig

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
        index_loss = 0.0
        for i in range(cfg.n_layers):
            x = WindowMoEBlock(
                cfg, windowed=cfg.windowed(i), rotate=cfg.rotated(i),
                name=f"block_{i}",
            )(x)
            if cfg.index_top_k:
                x, layer_loss = x
                with jax.named_scope("index_proj"):
                    index_loss = index_loss + layer_loss
        with jax.named_scope("norm"):
            x = RMSNorm(cfg.eps, cfg.dtype, name="final_norm")(x)
        with jax.named_scope("head"):
            logits = jnp.dot(
                x, head.astype(cfg.dtype), preferred_element_type=jnp.float32
            )
        return (logits, index_loss) if cfg.index_top_k else logits
