"""Causal language model with Gated DeltaNet layers beside full-attention
layers under a dense SwiGLU FFN, the mixers' heads held by share, on
``models/linear_dense.LinearDenseLM`` (untied head over the vocabulary
slice).

Config keys are those of the published ``config.json`` of the Olmo-Hybrid
layer (``hidden_size``, ``intermediate_size``, ``layer_types``,
``num_attention_heads``, ``linear_num_key_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``linear_allow_neg_eigval``, ...). The chip's share is the
configuration's: ``num_attention_heads`` (= ``num_key_value_heads`` =
``linear_num_key_heads`` = ``linear_num_value_heads``) heads are HELD here,
out of ``share.chips_per_layer`` times as many the model is parametrised
with, heads ``share.chip * held`` on; ``vocab_size`` is the slice;
``layer_types`` is the published list, of which the ``num_hidden_layers``
layers here read the first entries. ``assumed.<key>.value`` gives what the
catalog lacks (the initialisation).

Traffic: ``data.next_token_shift`` is 1, so a batch carries ``tokens [B,
seq_len + 1]``. Loss: the mean next-token cross entropy over every
position.

FLOPs per token: ``lib/flops_linear_dense.train_flops_per_token`` (6 N
over the matmul parameters, the full layers' score and value matmuls not
halved for the mask, the Gated DeltaNet layers' recurrence).

``controls(config, traffic)`` gives ``benchmark/controls.py`` this family's
altered builds, each ONE departure of the reference from the equations:
computed in bfloat16 throughout, one precision below the configuration's;
the decay dropped (``g = 0``); ``beta`` without its factor 2; the
convolution skipped; the q / k norm of the full layers skipped.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.lib import plain_linear_dense as plain
from benchmark.lib.family import Family, matmul_params
from benchmark.lib.flops_linear_dense import (
    FULL, LINEAR, layer_kinds, train_flops_per_token,
)


def sizes(config: dict) -> dict:
    """The model's sizes under the program's names, from the published
    keys, the share and the assumed values."""
    share, assumed = config["share"], config["assumed"]
    held = config["num_attention_heads"]
    same = ("num_key_value_heads", "linear_num_key_heads",
            "linear_num_value_heads")
    if any(config[key] != held for key in same) or held != share["heads_held"]:
        raise ValueError(
            f"this family's mixers hold the same {held} heads: {same}, "
            "share.heads_held"
        )
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("this family's full layers rotate nothing")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        layer_types=tuple(layer_kinds(config)),
        n_heads=share["chips_per_layer"] * held, heads_held=held,
        first_head=share["chip"] * held,
        head_dim=config["hidden_size"] // (share["chips_per_layer"] * held),
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        conv_size=config["linear_conv_kernel_dim"],
        neg_eigval=config["linear_allow_neg_eigval"],
        d_ff=config["intermediate_size"], eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"]["value"],
    )


def build(config: dict, traffic: dict, *, reference_dtype=jnp.float32,
          departure: str = "") -> Family:
    """``reference_dtype`` / ``departure`` are for the controls; a cell is
    built without them."""
    from horovod_tpu.models.linear_dense import (
        LinearDenseConfig, LinearDenseLM, lm_loss,
    )

    cfg = LinearDenseConfig(**sizes(config))
    seq_len = traffic["seq_len"]
    if traffic["data"].get("next_token_shift") != 1:
        raise ValueError("data.next_token_shift must be 1")
    model = LinearDenseLM(cfg)
    # Parameters depend on neither path nor the sequence length: draw them
    # through XLA attention and the recurrence on 8 positions, so that
    # set-up compiles no kernel it will never run.
    init_model = LinearDenseLM(
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
        layer_types=cfg.layer_types, heads=cfg.heads_held,
        head_dim=cfg.head_dim, key_dim=cfg.gdn_key_dim,
        value_dim=cfg.gdn_value_dim, eps=cfg.eps,
        scan_group=min(128, seq_len), q_block=min(256, seq_len),
        dtype=reference_dtype, departure=departure,
    )

    def reference_loss(params, batch):
        return plain.loss(params, batch["tokens"], z)

    def flops_per_token(params):
        # The embedding is a lookup; the head multiplies every token once.
        return train_flops_per_token(
            n_matmul_params=matmul_params(params, {"embed"}),
            n_linear_layers=cfg.layer_types.count(LINEAR),
            n_full_layers=cfg.layer_types.count(FULL), seq_len=seq_len,
            n_heads=cfg.heads_held, head_dim=cfg.head_dim,
            d_k=cfg.gdn_key_dim, d_v=cfg.gdn_value_dim,
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size,
        # two kernel families: flash_ms holds both, so the harness's own
        # flash share has nothing to divide; mha_flash_roofline and
        # gdn_roofline cost each by its kernels' names
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
        "beta_without_its_factor_2": build(
            config, traffic, departure="beta_unscaled"
        ),
        "convolution_skipped": build(config, traffic, departure="no_conv"),
        "qk_norm_skipped": build(config, traffic, departure="no_qk_norm"),
    }
    return {"none": sound, **{
        name: dataclasses.replace(sound, reference_loss=wrong.reference_loss)
        for name, wrong in altered.items()
    }}
