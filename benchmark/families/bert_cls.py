"""BERT encoder with a sequence-classification head on the first position
(``models/bert.BertModel(num_labels=n)``): the fine-tune of the Horovod
BERT examples.

Batch: ``tokens [B, S]`` padded to ``S``, ``attention_mask [B, S]``,
``labels [B]``. A padding mask sends attention down the XLA path
(``models/transformer.py``: ``use_flash and mask is None``), so this
family's cells bypass the flash kernels: ``flash`` is None.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import optax

from benchmark.lib import plain_transformer as plain
from benchmark.lib.family import Family, matmul_params
from benchmark.lib.flops import transformer_train_flops_per_token


def build(config: dict, traffic: dict) -> Family:
    from horovod_tpu.models.bert import BertConfig, BertModel

    cfg = BertConfig(
        vocab_size=config["vocab_size"],
        max_len=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        type_vocab_size=config["type_vocab_size"],
    )
    seq_len = traffic["seq_len"]
    num_labels = int(traffic["data"]["labels"]["num_labels"])
    if seq_len > cfg.max_len:
        raise ValueError(f"seq_len {seq_len} exceeds the position table")
    model = BertModel(cfg, num_labels=num_labels)
    init_model = BertModel(
        dataclasses.replace(cfg, use_flash=False), num_labels=num_labels
    )

    @jax.jit
    def init_params(key):
        tokens = jnp.zeros((1, 8), jnp.int32)
        return init_model.init(key, tokens, token_types=tokens)["params"]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits = model.apply(
            {"params": params}, tokens, token_types=jnp.zeros_like(tokens),
            attention_mask=batch["attention_mask"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]
        ).mean()

    def reference_loss(params, batch):
        tokens = batch["tokens"]
        h = plain.hidden_states(
            params["encoder"], tokens, n_layers=cfg.n_layers, causal=False,
            token_types=jnp.zeros_like(tokens),
            key_mask=batch["attention_mask"],
        )
        pooled = jnp.tanh(plain.dense(params["pooler"], h[:, 0]))
        return plain.cross_entropy(
            plain.dense(params["classifier"], pooled), batch["labels"]
        )

    def flops_per_token(params):
        # Pooler and classifier see one position per sequence; counting
        # them for every token overstates the total by under 1%.
        return transformer_train_flops_per_token(
            matmul_params(params, {"wte", "wpe", "wtt"}), cfg.n_layers,
            seq_len, cfg.d_model,
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size, flash=None,
    )
