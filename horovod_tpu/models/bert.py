"""BERT encoder (parity target: BASELINE.json config #3 — BERT-base
fine-tune; the reference runs it via ``examples/pytorch`` + torch
DistributedOptimizer)."""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .transformer import Transformer, TransformerConfig


@dataclasses.dataclass(frozen=True)
class BertConfig(TransformerConfig):
    vocab_size: int = 30522
    max_len: int = 512
    causal: bool = False
    type_vocab_size: int = 2

    @staticmethod
    def base(**kw) -> "BertConfig":
        return BertConfig(**kw)  # 110M defaults

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        base = dict(
            vocab_size=512, max_len=128, d_model=64, n_heads=4, n_layers=2,
            d_ff=128, causal=False, type_vocab_size=2,
        )
        base.update(kw)
        return BertConfig(**base)


class BertModel(nn.Module):
    """Encoder with MLM head and pooled [CLS] output.

    ``attention_mask`` (``[batch, seq]`` of 0/1) masks padding the way the
    reference's HF-based fine-tune example does.
    """

    cfg: BertConfig
    num_labels: Optional[int] = None  # set → classification head on [CLS]

    @nn.compact
    def __call__(self, tokens, *, token_types=None, attention_mask=None,
                 return_hidden=False):
        """``return_hidden=True`` (MLM path only) returns the post-``mlm_ln``
        activations instead of decoder logits, for the chunked loss
        (``ops.losses.fused_cross_entropy`` against the ``mlm_decoder``
        kernel/bias). Init with the default path so decoder params exist."""
        cfg = self.cfg
        mask = None
        if attention_mask is not None:
            # [B, S] -> [B, 1, 1, S] broadcast over heads & query positions.
            with jax.named_scope("attn_xla"):
                mask = attention_mask[:, None, None, :].astype(bool)
        h = Transformer(cfg, name="encoder")(
            tokens, token_types=token_types, mask=mask
        )
        if self.num_labels is not None:
            with jax.named_scope("head"):
                pooled = nn.tanh(
                    nn.Dense(cfg.d_model, dtype=cfg.dtype, name="pooler")(
                        h[:, 0]
                    )
                )
                return nn.Dense(
                    self.num_labels, dtype=jnp.float32, name="classifier"
                )(pooled)
        # MLM head: transform + tied decoder would need wte; use a dense
        # decoder (capability parity, not checkpoint compatibility).
        with jax.named_scope("head"):
            x = nn.gelu(
                nn.Dense(cfg.d_model, dtype=cfg.dtype, name="mlm_dense")(h)
            )
        with jax.named_scope("norm"):
            x = nn.LayerNorm(dtype=cfg.dtype, name="mlm_ln")(x)
        if return_hidden:
            return x
        # fp32 logits: measured r4 that bf16 logits do not change the step
        # time (the vocab matmuls are compute-bound, and XLA fuses the
        # softmax recompute into the dW matmul rather than re-reading a
        # dlogits buffer), so the numerically safer dtype stays.
        with jax.named_scope("head"):
            return nn.Dense(
                cfg.vocab_size, dtype=jnp.float32, name="mlm_decoder"
            )(x)
