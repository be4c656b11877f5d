"""Causal language model on ``models/gpt2.GPT2LMModel`` (tied head).

Config keys are those of the published ``config.json`` (``n_layer``,
``n_embd``, ``n_head``, ``n_positions``, ``vocab_size``, ``n_inner``).
Batch: ``tokens [B, seq_len + 1]``; loss: next-token cross-entropy, mean
over every position.
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
    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel

    d_model = config["n_embd"]
    cfg = GPT2Config(
        vocab_size=config["vocab_size"], max_len=config["n_positions"],
        d_model=d_model, n_heads=config["n_head"],
        n_layers=config["n_layer"], d_ff=config["n_inner"] or 4 * d_model,
    )
    seq_len = traffic["seq_len"]
    if seq_len > cfg.max_len:
        raise ValueError(f"seq_len {seq_len} exceeds n_positions")
    model = GPT2LMModel(cfg)
    # Parameters do not depend on the attention path or the sequence
    # length: draw them through XLA attention on 8 positions, so that
    # set-up compiles no kernel it will never run.
    init_model = GPT2LMModel(dataclasses.replace(cfg, use_flash=False))

    @jax.jit
    def init_params(key):
        return init_model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        logits = model.apply({"params": params}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]
        ).mean()

    def reference_loss(params, batch):
        tokens = batch["tokens"]
        p = params["transformer"]
        hidden = plain.hidden_states(
            p, tokens[:, :-1], n_layers=cfg.n_layers, causal=True
        )
        return plain.cross_entropy(plain.tied_logits(p, hidden), tokens[:, 1:])

    def flops_per_token(params):
        # The tied embedding is the head's matrix and counts; the position
        # table is a lookup and does not.
        return transformer_train_flops_per_token(
            matmul_params(params, {"wpe"}), cfg.n_layers, seq_len, d_model
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size,
        flash={
            "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
            "seq_len": seq_len, "head_dim": d_model // cfg.n_heads,
            "causal": True,
        },
    )
