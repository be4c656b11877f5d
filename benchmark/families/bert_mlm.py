"""BERT encoder with the program's masked-LM head
(``models/bert.BertModel``): dense + GELU + LayerNorm + an UNTIED dense
decoder with fp32 logits, loss at every position (the configuration file's
``assumed`` says how that differs from the published head).

Config keys are those of the published ``config.json``. Batch: ``tokens
[B, S]``, ``labels [B, S]``; no attention mask, so attention takes the
flash kernels.
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
    if seq_len > cfg.max_len:
        raise ValueError(f"seq_len {seq_len} exceeds the position table")
    model = BertModel(cfg)
    init_model = BertModel(dataclasses.replace(cfg, use_flash=False))

    @jax.jit
    def init_params(key):
        tokens = jnp.zeros((1, 8), jnp.int32)
        return init_model.init(key, tokens, token_types=tokens)["params"]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits = model.apply(
            {"params": params}, tokens, token_types=jnp.zeros_like(tokens)
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]
        ).mean()

    def reference_loss(params, batch):
        tokens = batch["tokens"]
        h = plain.hidden_states(
            params["encoder"], tokens, n_layers=cfg.n_layers, causal=False,
            token_types=jnp.zeros_like(tokens),
        )
        h = plain.gelu_tanh(plain.dense(params["mlm_dense"], h))
        h = plain.layer_norm(params["mlm_ln"], h)
        return plain.cross_entropy(
            plain.dense(params["mlm_decoder"], h), batch["labels"]
        )

    def flops_per_token(params):
        # The decoder is untied, so the token embedding is a pure lookup.
        return transformer_train_flops_per_token(
            matmul_params(params, {"wte", "wpe", "wtt"}), cfg.n_layers,
            seq_len, cfg.d_model,
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size,
        flash={
            "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
            "seq_len": seq_len, "head_dim": cfg.d_model // cfg.n_heads,
            "causal": False,
        },
    )
