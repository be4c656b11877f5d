"""Latent attention, routed experts held by share, multi-token prediction:
the DeepSeek-V3 layer as a causal language model.

One model for every configuration of that family (the benchmark's
configuration file gives the published sizes); "supported" means training,
on the normal path (``dp.make_train_step`` on :func:`lm_loss`), of ONE
CHIP'S SHARE of a layer that expert parallelism divides over several: this
chip holds ``n_experts_held`` of each layer's ``n_experts`` routed experts
(``first_expert`` on) and a slice of the vocabulary, routes over all
experts, and computes the part of the result that its experts give
(``parallel/ep.local_experts``). What the absent experts would add is left
out and the partial sum goes on. Serving (the latent cache) is not built.

Equations (no bias anywhere, ``eps`` 1e-6). Block::

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))

Attention (``H`` heads, ``n`` = ``qk_nope_dim``, ``r`` = ``qk_rope_dim``,
``v`` = ``v_dim``)::

    c_q = RMSNorm(x W_qa)                      [q_lora_rank]
    q = c_q W_qb            -> H x (n + r):    [q_nope | q_rope]
                   (``q_lora_rank=None``: q = x W_q, one projection)
    [c_kv | k_r] = x W_kva                     [kv_lora_rank + r]
    [k_nope | v] = RMSNorm(c_kv) W_kvb  -> H x (n + v)
    q_rope, k_r <- rotary(theta, interleaved pairs, no scaling); every head
                   of a position shares the one k_r (``use_rope=False``,
                   NoPE: nothing is rotated)
    q = [q_nope | q_rope], k = [k_nope | k_r]
    out = concat_H(causal softmax(q k^T / sqrt(n + r)) v) W_o

so q / k heads are ``n + r`` wide and v / out heads ``v`` wide. The flash
kernels take ``W_kvb``'s output and ``k_r`` as they are
(``ops/pallas_kernels.flash_attention_latent``), and ``W_qb``'s output too:
they rotate ``q_rope`` themselves (``q_rotary``) and hand back the gradient
of the projection's output. ``k`` and a rotated ``q`` are built only for the
XLA path.

Expert layer (``E`` experts, ``k`` = ``top_k``)::

    s = sigmoid(x W_r)  in fp32 over all E;  chosen = the k largest of s + b
    w = s_chosen / sum(s_chosen) * routed_scale
    y = sum_j w_j E_{chosen_j}(x) + E_shared(x),  E(x) = W_d (silu(W_g x) * W_u x)

``b`` (``e_score_correction_bias``) is a constant zero buffer here: it takes
no gradient, no balance update is made and there is no auxiliary loss. The
first ``n_dense_layers`` blocks use the same ``E`` as one dense FFN of width
``d_ff_dense``. Output: final RMSNorm, an untied head over the vocabulary
slice, logits in fp32.

Multi-token prediction (DeepSeek-V3 report, section 2.2; one module)::

    h' = W_eh [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]
    one expert block, its own final RMSNorm, the shared embedding and head
    loss = CE(main, t_{i+1}) + mtp_weight CE(mtp, t_{i+2})

so a batch carries ``seq_len + 1 + n_mtp`` tokens: the model takes all but
the last, :func:`lm_loss` all of them.

Departures from the published model, all stated in the configuration file:
rotary is applied to adjacent pairs directly (the published code permutes
to halves first; the scores are the same), ``b`` is not updated, weights
are drawn N(0, ``init_std``), parameters are fp32 and computed in bf16 with
the router, softmax, norms and logits in fp32.

Scopes (``jax.named_scope``; ``docs/api.md`` has the table). Every
operation of ``apply`` lies under exactly one of: ``embed`` (the token
lookups and, in the multi-token module, the concatenation and ``W_eh``),
``norm`` (the blocks' RMSNorms and residual sums, the expert layer's sum of
routed and shared output, the norms before the heads), ``mla_proj`` (the
six projections, their two norms, the shared key's rotary; off the flash
path also q's rotary and the concatenations that build q and k),
``attn_layout`` (the reshapes between the projections and the kernels, and
the kernels' entry's own glue), ``attn_xla`` (attention where flash is
bypassed), ``mlp`` (``GatedMlp``: the dense FFN and the shared experts),
``moe_route``, ``moe_experts`` (``parallel/ep.py``), ``head`` (the logits
matmuls). The Mosaic kernels carry none of them. ``mtp`` lies over the
whole multi-token module, and so over a part of each of the others.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..context import device_platform
from ..parallel import ep
from .transformer import (  # noqa: F401  (rotary, lm_loss: re-exported)
    GatedMlp, RMSNorm, dot_product_attention, lm_loss, rotary,
    rotary_tables,
)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 129280  # rows of the vocabulary held here
    d_model: int = 2048
    n_layers: int = 40
    n_dense_layers: int = 1  # leading blocks with a dense FFN
    n_heads: int = 32
    # None: one ``q`` projection (no ``q_a`` / ``q_norm`` / ``q_b``)
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 32e6
    # False (NoPE): nothing is rotated, the shared key goes to the kernels
    # as ``kv_a`` leaves it
    use_rope: bool = True
    d_ff_dense: int = 7168
    d_ff_expert: int = 768
    n_experts: int = 256  # the router's width
    n_experts_held: int = 256  # routed experts whose weights live here
    first_expert: int = 0  # ... and the first of them
    top_k: int = 8
    routed_scale: float = 2.5
    n_shared_experts: int = 1
    n_mtp: int = 1  # multi-token-prediction modules (0 or 1)
    mtp_weight: float = 0.3
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    # None: the flash kernels where the world's devices are TPUs
    use_flash: Optional[bool] = None

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @staticmethod
    def tiny(**kw) -> "LatentMoEConfig":
        base = dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=2,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
            v_dim=16, d_ff_dense=96, d_ff_expert=24, n_experts=32,
            n_experts_held=8, top_k=4,
        )
        base.update(kw)
        return LatentMoEConfig(**base)


def _init(cfg: LatentMoEConfig):
    return nn.initializers.normal(cfg.init_std)


class LatentAttention(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, n, r, v = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=cfg.dtype, name=name,
            kernel_init=_init(cfg),
        )
        norm = lambda name: RMSNorm(cfg.eps, cfg.dtype, name=name)  # noqa: E731
        use_flash = cfg.use_flash
        if use_flash is None:
            use_flash = device_platform() == "tpu"
        with jax.named_scope("mla_proj"):
            if cfg.q_lora_rank is None:
                q = dense(h * (n + r), "q")(x)
            else:
                q = dense(h * (n + r), "q_b")(
                    norm("q_norm")(dense(cfg.q_lora_rank, "q_a")(x))
                )
            kv_a = dense(cfg.kv_lora_rank + r, "kv_a")(x)
            # [k_nope | v] a head, heads on the lanes: as the kernels take it
            kv = dense(h * (n + v), "kv_b")(
                norm("kv_norm")(kv_a[..., :cfg.kv_lora_rank])
            )
            k_rope = kv_a[..., cfg.kv_lora_rank:]
            if cfg.use_rope:
                k_rope = rotary(k_rope, theta=cfg.rope_theta)
        if use_flash:
            from ..ops.pallas_kernels import QRotary, flash_attention_latent

            # q as ``q_b`` leaves it: the kernels rotate each head's last
            # ``r`` lanes and hand back the gradient of ``q_b``'s output
            out, _ = flash_attention_latent(
                q, kv, k_rope, causal=True, n_heads=h, q_rotary=QRotary(
                    *rotary_tables(s, r, theta=cfg.rope_theta), start=n
                ) if cfg.use_rope else None,
            )
        else:
            with jax.named_scope("mla_proj"):
                q = q.reshape(b, s, h, n + r)
                if cfg.use_rope:
                    q = jnp.concatenate([
                        q[..., :n], rotary(q[..., n:], theta=cfg.rope_theta)
                    ], axis=-1)
                kv = kv.reshape(b, s, h, n + v)
                k = jnp.concatenate([
                    kv[..., :n],
                    jnp.broadcast_to(k_rope[:, :, None], (b, s, h, r)),
                ], axis=-1)
            with jax.named_scope("attn_xla"):
                out = dot_product_attention(q, k, kv[..., n:], causal=True)
            with jax.named_scope("attn_layout"):
                out = out.reshape(b, s, h * v)
        with jax.named_scope("mla_proj"):
            return dense(cfg.d_model, "o")(out)


class RoutedExperts(nn.Module):
    """The expert layer's FFN: the router over all experts, the held
    experts' part, the shared expert(s)."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        held, f = cfg.n_experts_held, cfg.d_ff_expert
        router = self.param(
            "router", _init(cfg), (d, cfg.n_experts), jnp.float32
        )
        # e_score_correction_bias: a constant buffer, not a parameter
        with jax.named_scope("moe_route"):
            score_bias = jnp.zeros((cfg.n_experts,), jnp.float32)
        stacked = lambda name, shape: self.param(  # noqa: E731
            name, _init(cfg), shape, jnp.float32
        )
        gate = stacked("experts_gate", (held, d, f))
        up = stacked("experts_up", (held, d, f))
        down = stacked("experts_down", (held, f, d))
        with jax.named_scope("norm"):  # free: the token-major view
            tokens = x.reshape(b * s, d)
        with jax.named_scope("moe_route"):
            chosen, weights = ep.topk_route(
                tokens, router, score_bias, top_k=cfg.top_k,
                scale=cfg.routed_scale,
            )
        out = ep.local_experts(
            tokens, chosen, weights, gate, up, down,
            first_expert=cfg.first_expert, n_experts=cfg.n_experts,
        )
        with jax.named_scope("norm"):
            out = out.reshape(b, s, d)
        if cfg.n_shared_experts:
            shared = GatedMlp(
                cfg.n_shared_experts * f, cfg.dtype, _init(cfg),
                name="shared",
            )(x)
            with jax.named_scope("norm"):  # a sum of branches, as a residual
                out = out + shared
        return out


class LatentMoEBlock(nn.Module):
    cfg: LatentMoEConfig
    dense_ffn: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.eps, cfg.dtype, name=name)  # noqa: E731
        with jax.named_scope("norm"):
            h = norm("attn_norm")(x)
        h = LatentAttention(cfg, name="attn")(h)
        with jax.named_scope("norm"):
            x = x + h
            h = norm("ffn_norm")(x)
        if self.dense_ffn:
            h = GatedMlp(cfg.d_ff_dense, cfg.dtype, _init(cfg), name="ffn")(h)
        else:
            h = RoutedExperts(cfg, name="ffn")(h)
        with jax.named_scope("norm"):
            return x + h


class LatentMoELM(nn.Module):
    """``tokens [B, S + n_mtp] -> (logits, mtp_logits)``, both fp32
    ``[B, S, vocab]``; ``logits[:, i]`` predicts ``tokens[:, i + 1]`` and
    ``mtp_logits[:, i]`` (None without the module) ``tokens[:, i + 2]``."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        s = tokens.shape[1] - cfg.n_mtp
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="embed",
            embedding_init=_init(cfg),
        )
        head = self.param(
            "head", _init(cfg), (cfg.d_model, cfg.vocab_size), jnp.float32
        )

        def logits_of(hidden, name):
            with jax.named_scope("norm"):
                hidden = RMSNorm(cfg.eps, cfg.dtype, name=name)(hidden)
            with jax.named_scope("head"):
                return jnp.dot(
                    hidden, head.astype(cfg.dtype),
                    preferred_element_type=jnp.float32,
                )

        with jax.named_scope("embed"):
            x = embed(tokens[:, :s])
        for i in range(cfg.n_layers):
            x = LatentMoEBlock(
                cfg, dense_ffn=i < cfg.n_dense_layers, name=f"block_{i}"
            )(x)
        logits = logits_of(x, "final_norm")
        if not cfg.n_mtp:
            return logits, None
        with jax.named_scope("mtp"):
            mtp_norm = lambda name: RMSNorm(  # noqa: E731
                cfg.eps, cfg.dtype, name=f"mtp_{name}_norm"
            )
            with jax.named_scope("norm"):
                hidden = mtp_norm("hidden")(x)
            with jax.named_scope("embed"):
                shifted = embed(tokens[:, 1:s + 1])
            with jax.named_scope("norm"):
                shifted = mtp_norm("embed")(shifted)
            with jax.named_scope("embed"):  # W_eh: the module's input
                merged = nn.Dense(
                    cfg.d_model, use_bias=False, dtype=cfg.dtype,
                    name="mtp_proj", kernel_init=_init(cfg),
                )(jnp.concatenate([hidden, shifted], axis=-1))
            merged = LatentMoEBlock(cfg, name="mtp_block")(merged)
            return logits, logits_of(merged, "mtp_final_norm")
