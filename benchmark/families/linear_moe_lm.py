"""Causal language model with Kimi Delta Attention layers beside a NoPE
latent-attention layer and routed experts held by share, on
``models/linear_moe.LinearMoELM`` (untied head over the vocabulary slice).

Config keys are those of the published ``config.json`` of the Kimi-Linear
layer (``hidden_size``, ``linear_attn_config`` with its two 1-indexed
layout lists, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``num_experts``, ``num_experts_per_token``, ...). The
chip's share is the configuration's: ``num_experts`` experts are HELD here,
out of the ``share.router_width`` the router scores, numbers ``share.chip *
num_experts`` on; ``vocab_size`` is the slice; the two layout lists are the
published ones, of which the ``num_hidden_layers`` layers here read the
first entries. ``assumed.<key>.value`` gives what the catalog lacks (the
gates' rank, the initialisation).

Traffic: ``data.next_token_shift`` is 1, so a batch carries ``tokens [B,
seq_len + 1]``. Loss: the mean next-token cross entropy over every
position.

FLOPs per token: ``lib/flops_linear_moe.train_flops_per_token`` (6 N with
the expected held share of the routed experts, the latent layers' score
and value matmuls not halved for the mask, the KDA layers' recurrence).

``controls(config, traffic)`` gives ``benchmark/controls.py`` this family's
altered builds, each ONE departure of the reference from the equations:
computed in bfloat16 throughout, one precision below the configuration's;
the decay dropped (``g = 0``); ``beta`` fixed at 1; the convolution
skipped.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.lib import plain_linear_moe as plain
from benchmark.lib.family import Family, matmul_params
from benchmark.lib.flops_linear_moe import layer_kinds, train_flops_per_token

ROUTED = ("experts_gate", "experts_up", "experts_down")


def sizes(config: dict) -> dict:
    """The model's sizes under the program's names, from the published
    keys, the share and the assumed values."""
    share, assumed = config["share"], config["assumed"]
    linear, held = config["linear_attn_config"], config["num_experts"]
    if not config["mla_use_nope"] or config["q_lora_rank"] is not None:
        raise ValueError("this family's latent layer is NoPE, no q rank")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        kda_layers=tuple(linear["kda_layers"]),
        full_attn_layers=tuple(linear["full_attn_layers"]),
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        conv_size=linear["short_conv_kernel_size"],
        gate_rank=assumed["gate_rank"]["value"],
        n_heads=config["num_attention_heads"], q_lora_rank=None,
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], use_rope=False,
        d_ff_dense=config["intermediate_size"],
        d_ff_expert=config["moe_intermediate_size"],
        n_experts=share["router_width"], n_experts_held=held,
        first_expert=share["chip"] * held,
        top_k=config["num_experts_per_token"],
        routed_scale=config["routed_scaling_factor"],
        n_shared_experts=config["num_shared_experts"],
        eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"]["value"],
    )


def build(config: dict, traffic: dict, *, reference_dtype=jnp.float32,
          departure: str = "") -> Family:
    """``reference_dtype`` / ``departure`` are for the controls; a cell is
    built without them."""
    from horovod_tpu.models.linear_moe import (
        LinearMoEConfig, LinearMoELM, lm_loss,
    )

    cfg = LinearMoEConfig(**sizes(config))
    seq_len = traffic["seq_len"]
    if traffic["data"].get("next_token_shift") != 1:
        raise ValueError("data.next_token_shift must be 1")
    if cfg.n_experts != config["share"]["chips_per_layer"] * cfg.n_experts_held:
        raise ValueError("router_width != chips_per_layer * experts held")
    kinds = layer_kinds(config)
    model = LinearMoELM(cfg)
    # Parameters depend on neither path nor the sequence length: draw them
    # through XLA attention and the recurrence on 8 positions, so that
    # set-up compiles no kernel it will never run.
    init_model = LinearMoELM(
        dataclasses.replace(cfg, use_flash=False, use_kernel=False)
    )

    @jax.jit
    def init_params(key):
        return init_model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        # the last token is a target only, as in every LM batch
        logits = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, None, tokens, mtp_weight=0.0)

    z = plain.Sizes(
        n_layers=cfg.n_layers, n_dense_layers=cfg.n_dense_layers,
        kda_layers=cfg.kda_layers, kda_heads=cfg.kda_heads,
        kda_head_dim=cfg.kda_head_dim, n_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_dim=cfg.v_dim,
        first_expert=cfg.first_expert, top_k=cfg.top_k,
        routed_scale=cfg.routed_scale, eps=cfg.eps,
        scan_group=min(128, seq_len), q_block=min(256, seq_len),
        dtype=reference_dtype, departure=departure,
    )

    def reference_loss(params, batch):
        return plain.loss(params, batch["tokens"], z)

    def flops_per_token(params):
        # The embedding is a lookup; the routed experts count by their
        # expected share; the head multiplies every token once.
        return train_flops_per_token(
            n_always_params=matmul_params(params, {"embed", *ROUTED}),
            n_expert_params=3 * cfg.d_model * cfg.d_ff_expert,
            n_expert_layers=cfg.n_layers - cfg.n_dense_layers,
            top_k=cfg.top_k, n_held=cfg.n_experts_held,
            n_experts=cfg.n_experts, n_kda_layers=kinds.count("kda"),
            n_latent_layers=kinds.count("latent"), seq_len=seq_len,
            n_heads=cfg.n_heads, kda_head_dim=cfg.kda_head_dim,
            qk_dim=cfg.qk_nope_dim + cfg.qk_rope_dim, v_dim=cfg.v_dim,
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size,
        # two kernel families: lib/flops.flash_attention_cost has neither;
        # the recurrence is costed by layer_metrics/kda_roofline
        flash=None,
    )


def controls(config: dict, traffic: dict) -> dict:
    """name -> the family with ONE side altered, and ``"none"`` -> the
    sound family whose other side each shares (``benchmark/controls.py``
    compares an altered side with the sound other side at the cell's
    tolerance)."""
    sound = build(config, traffic)
    altered = {
        "reference_in_bfloat16": build(
            config, traffic, reference_dtype=jnp.bfloat16
        ),
        "decay_dropped": build(config, traffic, departure="no_decay"),
        "beta_fixed_at_one": build(config, traffic, departure="beta_one"),
        "convolution_skipped": build(config, traffic, departure="no_conv"),
    }
    return {"none": sound, **{
        name: dataclasses.replace(sound, reference_loss=wrong.reference_loss)
        for name, wrong in altered.items()
    }}
