"""Causal language model with window and full attention layers mixed,
query heads in groups, routed experts held by share and the router ahead
of the attention, on ``models/window_moe.WindowMoELM`` (untied head over
the vocabulary slice).

Config keys are those of the published ``config.json`` of the SmallThinker
layer (``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``moe_ffn_hidden_size``,
``moe_num_primary_experts``, ``moe_num_active_primary_experts``,
``sliding_window_size``, ``sliding_window_layout``, ``rope_layout``, ...).
The chip's share is the configuration's: ``moe_num_primary_experts``
experts are HELD here, out of the ``share.router_width`` the router scores,
numbers ``share.chip * moe_num_primary_experts`` on; ``vocab_size`` is the
slice; the two layouts are the published lists, of which the
``num_hidden_layers`` layers here read the first entries.
``assumed.<key>.value`` gives what the catalog lacks (the initialisation).

Traffic: ``data.next_token_shift`` is 1, so a batch carries ``tokens [B,
seq_len + 1]``. Loss: the mean next-token cross entropy over every
position.

FLOPs per token: ``lib/flops_window_moe.train_flops_per_token`` (6 N with
the expected held share of the routed experts, plus attention over the
columns each layer's mask lets a row reach).

``controls(config, traffic)`` gives ``benchmark/controls.py`` this family's
altered builds: the reference computed in bfloat16 throughout, one
precision below the configuration's; the program with the window ignored
(every layer attends to all earlier positions); the program's router
reading ``RMSNorm2(h')``, the experts' input, where the configuration says
the attention's.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.lib import plain_window_moe as plain
from benchmark.lib.family import Family, matmul_params
from benchmark.lib.flops_window_moe import (
    layer_windows, train_flops_per_token,
)

ROUTED = ("experts_gate", "experts_up", "experts_down")


def sizes(config: dict) -> dict:
    """The model's sizes under the program's names, from the published
    keys, the share and the assumed values."""
    share, assumed = config["share"], config["assumed"]
    held, layers = config["moe_num_primary_experts"], config["num_hidden_layers"]
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=layers, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window_size"],
        window_layout=tuple(config["sliding_window_layout"][:layers]),
        rope_layout=tuple(config["rope_layout"][:layers]),
        rope_theta=float(config["rope_theta"]),
        d_ff_expert=config["moe_ffn_hidden_size"],
        n_experts=share["router_width"], n_experts_held=held,
        first_expert=share["chip"] * held,
        top_k=config["moe_num_active_primary_experts"],
        eps=config["rms_norm_eps"],
        init_std=assumed["initializer_range"]["value"],
    )


def build(config: dict, traffic: dict, *,
          reference_dtype=jnp.float32) -> Family:
    """``reference_dtype`` is for a control; a cell is built without it."""
    from horovod_tpu.models.window_moe import (
        WindowMoEConfig, WindowMoELM, lm_loss,
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
        logits = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, None, tokens, mtp_weight=0.0)

    z = plain.Sizes(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, window=cfg.window,
        window_layout=cfg.window_layout, rope_layout=cfg.rope_layout,
        rope_theta=cfg.rope_theta, first_expert=cfg.first_expert,
        top_k=cfg.top_k, eps=cfg.eps, dtype=reference_dtype,
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
            windows=layer_windows(config), seq_len=seq_len, n_heads=cfg.n_heads,
            head_dim=cfg.head_dim,
        )

    return Family(
        init_params=init_params, loss_fn=loss_fn,
        reference_loss=reference_loss, flops_per_token=flops_per_token,
        vocab_size=cfg.vocab_size,
        # a band and two head counts: lib/flops.flash_attention_cost has
        # neither; this family's kernels are costed by
        # layer_metrics/gqa_flash_roofline
        flash=None,
    )


def _with_late_router(loss_fn):
    """The program with each layer's router reading what its experts read,
    ``RMSNorm2(h')``: the routing is put off until the expert layer is
    reached and made from the tokens that arrive there."""
    from horovod_tpu.parallel import ep

    def altered(params, batch):
        route, experts, waiting = ep.topk_route, ep.local_experts, []

        def put_off(tokens, router, score_bias, **how):
            waiting.append((router, score_bias, how))
            return None, None

        def route_then_compute(tokens, chosen, weights, *stacks, **share):
            router, score_bias, how = waiting.pop()
            chosen, weights = route(tokens, router, score_bias, **how)
            return experts(tokens, chosen, weights, *stacks, **share)

        ep.topk_route, ep.local_experts = put_off, route_then_compute
        try:  # while it is traced
            return loss_fn(params, batch)
        finally:
            ep.topk_route, ep.local_experts = route, experts

    return altered


def controls(config: dict, traffic: dict) -> dict:
    """name -> the family with ONE side altered, and ``"none"`` -> the
    sound family whose other side each shares (``benchmark/controls.py``
    compares an altered side with the sound other side at the cell's
    tolerance)."""
    sound = build(config, traffic)
    in_bfloat16 = build(config, traffic, reference_dtype=jnp.bfloat16)
    no_window = build({
        **config,
        "sliding_window_layout": [0] * len(config["sliding_window_layout"]),
    }, traffic)
    return {
        "none": sound,
        "reference_in_bfloat16": dataclasses.replace(
            sound, reference_loss=in_bfloat16.reference_loss
        ),
        "window_ignored": dataclasses.replace(
            sound, loss_fn=no_window.loss_fn
        ),
        "router_reads_ffn_norm": dataclasses.replace(
            sound, loss_fn=_with_late_router(sound.loss_fn)
        ),
    }
