"""Causal language model with latent attention, routed experts held by
share and a multi-token-prediction module, on
``models/latent_moe.LatentMoELM`` (untied head over the vocabulary slice).

Config keys are those of the published ``config.json`` of the DeepSeek-V3
layer (``hidden_size``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``n_routed_experts``, ``num_experts_per_tok``, ...). The chip's share is
the configuration's: ``n_routed_experts`` experts are HELD here, out of the
``share.router_width`` the router scores, numbers ``share.chip *
n_routed_experts`` on; ``vocab_size`` is the slice. ``assumed.<key>.value``
gives what the catalog lacks (the multi-token loss weight, the
initialisation).

Traffic: ``data.next_token_shift`` is 1 + ``num_nextn_predict_layers``, so
a batch carries ``tokens [B, seq_len + 2]``. Loss: ``CE(main, t+1) + weight
CE(mtp, t+2)``, each a mean over every position.

FLOPs per token: ``lib/flops_latent_moe.train_flops_per_token`` (6 N with
the expected held share of the routed experts, plus attention at the two
head widths, not halved for the mask; the source is ``lib/flops.py``'s
convention, Kaplan et al. 2020 section 2.1 and PaLM appendix B).

``controls(config, traffic)`` gives ``benchmark/controls.py`` this family's
altered builds: the shared expert left out of the program, the program's
router scores in bfloat16 where the configuration says float32, and the
reference computed in bfloat16 throughout, one precision below the
configuration's. On the chip at the cell's limit the comparison of three
losses refuses the last and neither of the first two (PERF.md, PR 36).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.lib import plain_latent_moe as plain
from benchmark.lib.family import Family, matmul_params
from benchmark.lib.flops_latent_moe import train_flops_per_token

ROUTED = ("experts_gate", "experts_up", "experts_down")


def sizes(config: dict) -> dict:
    """The model's sizes under the program's names, from the published
    keys, the share and the assumed values."""
    share, assumed = config["share"], config["assumed"]
    held = config["n_routed_experts"]
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], rope_theta=float(config["rope_theta"]),
        d_ff_dense=config["intermediate_size"],
        d_ff_expert=config["moe_intermediate_size"],
        n_experts=share["router_width"], n_experts_held=held,
        first_expert=share["chip"] * held,
        top_k=config["num_experts_per_tok"],
        routed_scale=config["routed_scaling_factor"],
        n_shared_experts=config["n_shared_experts"],
        n_mtp=config["num_nextn_predict_layers"],
        mtp_weight=assumed["mtp_loss_weight"]["value"],
        eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"]["value"],
    )


def build(config: dict, traffic: dict, *,
          reference_dtype=jnp.float32) -> Family:
    """``reference_dtype`` is for a control; a cell is built without it."""
    from horovod_tpu.models.latent_moe import (
        LatentMoEConfig, LatentMoELM, lm_loss,
    )

    cfg = LatentMoEConfig(**sizes(config))
    seq_len = traffic["seq_len"]
    if traffic["data"].get("next_token_shift") != 1 + cfg.n_mtp:
        raise ValueError(
            "data.next_token_shift must be 1 + num_nextn_predict_layers"
        )
    if cfg.n_experts != config["share"]["chips_per_layer"] * cfg.n_experts_held:
        raise ValueError("router_width != chips_per_layer * experts held")
    model = LatentMoELM(cfg)
    # Parameters depend on neither the attention path nor the sequence
    # length: draw them through XLA attention on 8 positions, so that
    # set-up compiles no kernel it will never run.
    init_model = LatentMoELM(dataclasses.replace(cfg, use_flash=False))

    @jax.jit
    def init_params(key):
        tokens = jnp.zeros((1, 8 + cfg.n_mtp), jnp.int32)
        return init_model.init(key, tokens)["params"]

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        # the last token is a target only, as in every LM batch
        logits, mtp_logits = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, mtp_logits, tokens, mtp_weight=cfg.mtp_weight)

    z = plain.Sizes(
        n_layers=cfg.n_layers, n_dense_layers=cfg.n_dense_layers,
        n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
        v_dim=cfg.v_dim, rope_theta=cfg.rope_theta,
        first_expert=cfg.first_expert, top_k=cfg.top_k,
        routed_scale=cfg.routed_scale, n_mtp=cfg.n_mtp,
        mtp_weight=cfg.mtp_weight, eps=cfg.eps, dtype=reference_dtype,
    )

    def reference_loss(params, batch):
        return plain.loss(params, batch["tokens"], z)

    def flops_per_token(params):
        # The embedding is a lookup; the routed experts count by their
        # expected share; the head multiplies every token once for the
        # main logits and once more for the multi-token module's.
        always = matmul_params(params, {"embed", *ROUTED})
        always += cfg.n_mtp * int(params["head"].size)
        return train_flops_per_token(
            n_always_params=always,
            n_expert_params=3 * cfg.d_model * cfg.d_ff_expert,
            n_expert_layers=cfg.n_layers - cfg.n_dense_layers + cfg.n_mtp,
            top_k=cfg.top_k, n_held=cfg.n_experts_held,
            n_experts=cfg.n_experts,
            n_attention_blocks=cfg.n_layers + cfg.n_mtp, seq_len=seq_len,
            n_heads=cfg.n_heads, qk_dim=cfg.qk_dim, v_dim=cfg.v_dim,
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size,
        # two head widths: lib/flops.flash_attention_cost has one; this
        # family's kernels are costed by layer_metrics/mla_flash_roofline
        flash=None,
    )


def _without_shared_expert(loss_fn):
    def zero(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        return jnp.zeros_like(leaf) if "shared" in keys and "down" in keys \
            else leaf

    return lambda params, batch: loss_fn(
        jax.tree_util.tree_map_with_path(zero, params), batch
    )


def _with_bfloat16_router(loss_fn):
    from horovod_tpu.parallel import ep

    def route(x, router_kernel, score_bias, *, top_k, scale):
        half = jnp.bfloat16
        scores = jax.nn.sigmoid(
            jnp.dot(x.astype(half), router_kernel.astype(half))
        )
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(score_bias.astype(half)), top_k
        )
        picked = jnp.einsum(
            "tke,te->tk", jax.nn.one_hot(chosen, scores.shape[-1], dtype=half),
            scores,
        )
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
        return chosen.astype(jnp.int32), weights.astype(jnp.float32)

    def altered(params, batch):
        sound, ep.topk_route = ep.topk_route, route  # while it is traced
        try:
            return loss_fn(params, batch)
        finally:
            ep.topk_route = sound

    return altered


def controls(config: dict, traffic: dict) -> dict:
    """name -> the family with ONE side altered, and ``"none"`` -> the
    sound family whose other side each shares (``benchmark/controls.py``
    compares an altered side with the sound other side at the cell's
    tolerance)."""
    sound = build(config, traffic)
    in_bfloat16 = build(config, traffic, reference_dtype=jnp.bfloat16)
    return {
        "none": sound,
        "shared_expert_left_out": dataclasses.replace(
            sound, loss_fn=_without_shared_expert(sound.loss_fn)
        ),
        "router_in_bfloat16": dataclasses.replace(
            sound, loss_fn=_with_bfloat16_router(sound.loss_fn)
        ),
        "reference_in_bfloat16": dataclasses.replace(
            sound, reference_loss=in_bfloat16.reference_loss
        ),
    }
