"""Causal language model with learned sparse attention (a lightning indexer
scores every earlier position, each query keeps its ``topk`` best, the
indexer learns from the attention it prunes), query heads in groups and
routed experts held by share, on ``models/window_moe.WindowMoELM`` with its
indexer on (untied head over the vocabulary slice).

Config keys are those of the published ``config.json`` (``hidden_size``,
``head_dim``, ``num_attention_heads``, ``num_key_value_heads``,
``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``,
``rope_theta``, ``sa_config.{indexer_num_heads, indexer_head_dim, topk,
q_chunk_size, kv_chunk_size}``, ...). The chip's share is the
configuration's: ``num_experts`` experts are HELD here, out of the
``share.router_width`` the router scores, numbers ``share.chip *
num_experts`` on; ``vocab_size`` is the slice. ``assumed.<key>.value`` gives
what the catalog lacks (the initialisation). The two chunk sizes are the
tiles of the select kernel and change no result.

Traffic: ``data.next_token_shift`` is 1, so a batch carries ``tokens [B,
seq_len + 1]``. Loss: the mean next-token cross entropy over every position
plus the layers' index losses, ``lm_loss(logits, ...) + index_loss``.

FLOPs per token: ``lib/flops_select_moe.train_flops_per_token`` (6 N with
the expected held share of the routed experts, attention and the index loss
over the KEPT pairs, the indexer's scoring over every causal pair).

``controls(config, traffic)`` gives ``benchmark/controls.py`` this family's
altered builds: the reference computed in bfloat16 throughout, one
precision below the configuration's; the reference with the selection
ignored (every earlier position kept); the program without the index loss
in its loss.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.lib import plain_select_moe as plain
from benchmark.lib.family import Family, matmul_params
from benchmark.lib.flops_select_moe import train_flops_per_token

ROUTED = ("experts_gate", "experts_up", "experts_down")


def sizes(config: dict) -> dict:
    """The model's sizes under the program's names, from the published
    keys, the share and the assumed values."""
    share, assumed, sparse = (
        config["share"], config["assumed"], config["sa_config"]
    )
    held = config["num_experts"]
    if sparse["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key head")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window_layout=(0,), rope_layout=(1,),
        rope_theta=float(config["rope_theta"]),
        d_ff_expert=config["moe_intermediate_size"],
        n_experts=share["router_width"], n_experts_held=held,
        first_expert=share["chip"] * held,
        top_k=config["num_experts_per_tok"], eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"]["value"],
        router_input="ffn_norm", expert_activation=config["hidden_act"],
        qk_norm=True, index_top_k=sparse["topk"],
        index_heads=sparse["indexer_num_heads"],
        index_head_dim=sparse["indexer_head_dim"],
        index_blocks=(sparse["q_chunk_size"], sparse["kv_chunk_size"]),
    )


def build(config: dict, traffic: dict, *, reference_dtype=jnp.float32,
          departure: str = "", with_index_loss: bool = True) -> Family:
    """``reference_dtype`` / ``departure`` / ``with_index_loss`` are for the
    controls; a cell is built without them."""
    from horovod_tpu.models.window_moe import (
        WindowMoEConfig, WindowMoELM, lm_loss,
    )

    known = {f.name for f in dataclasses.fields(WindowMoEConfig)}
    if "index_top_k" not in known:
        # a program older than the select family: refuse at once
        raise SystemExit(
            "this program has no learned sparse attention "
            "(models/window_moe.WindowMoEConfig.index_top_k)"
        )
    cfg = WindowMoEConfig(**sizes(config))
    seq_len = traffic["seq_len"]
    if traffic["data"].get("next_token_shift") != 1:
        raise ValueError("data.next_token_shift must be 1")
    if cfg.n_experts != config["share"]["chips_per_layer"] * cfg.n_experts_held:
        raise ValueError("router_width != chips_per_layer * experts held")
    model = WindowMoELM(cfg)
    # Parameters depend on neither the attention path nor the sequence
    # length: draw them through XLA attention on 8 positions, so that
    # set-up compiles no kernel it will never run.
    init_model = WindowMoELM(dataclasses.replace(cfg, use_flash=False))

    @jax.jit
    def init_params(key):
        return init_model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        # the last token is a target only, as in every LM batch
        logits, index_loss = model.apply({"params": params}, tokens[:, :-1])
        loss = lm_loss(logits, None, tokens, mtp_weight=0.0)
        return loss + index_loss if with_index_loss else loss

    z = plain.Sizes(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        index_heads=cfg.index_heads, index_head_dim=cfg.index_head_dim,
        index_top_k=cfg.index_top_k, rope_theta=cfg.rope_theta,
        first_expert=cfg.first_expert, top_k=cfg.top_k, eps=cfg.eps,
        q_block=min(256, seq_len), dtype=reference_dtype,
        departure=departure,
    )

    def reference_loss(params, batch):
        return plain.loss(params, batch["tokens"], z)

    def flops_per_token(params):
        # The embedding is a lookup; the routed experts count by their
        # expected share; the head multiplies every token once.
        return train_flops_per_token(
            n_always_params=matmul_params(params, {"embed", *ROUTED}),
            n_expert_params=3 * cfg.d_model * cfg.d_ff_expert,
            n_layers=cfg.n_layers, top_k=cfg.top_k,
            n_held=cfg.n_experts_held, n_experts=cfg.n_experts,
            seq_len=seq_len, topk=cfg.index_top_k, n_heads=cfg.n_heads,
            head_dim=cfg.head_dim, index_heads=cfg.index_heads,
            index_head_dim=cfg.index_head_dim,
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size,
        # a mask and two head counts: lib/flops.flash_attention_cost has
        # neither; this family's kernels are costed by
        # layer_metrics/dsa_*_roofline
        flash=None,
    )


def controls(config: dict, traffic: dict) -> dict:
    """name -> the family with ONE side altered, and ``"none"`` -> the
    sound family whose other side each shares (``benchmark/controls.py``
    compares an altered side with the sound other side at the cell's
    tolerance)."""
    sound = build(config, traffic)
    in_bfloat16 = build(config, traffic, reference_dtype=jnp.bfloat16)
    keep_all = build(config, traffic, departure="keep_all")
    no_index_loss = build(config, traffic, with_index_loss=False)
    return {
        "none": sound,
        "reference_in_bfloat16": dataclasses.replace(
            sound, reference_loss=in_bfloat16.reference_loss
        ),
        "selection_ignored": dataclasses.replace(
            sound, reference_loss=keep_all.reference_loss
        ),
        "index_loss_left_out": dataclasses.replace(
            sound, loss_fn=no_index_loss.loss_fn
        ),
    }
