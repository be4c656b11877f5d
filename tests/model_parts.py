"""Tiny builds of the six model families and a walk over a traced step,
for the tests that hold the models' parts (``jax.named_scope``) to their
rules: ``test_step_tracing.py`` on the CPU, ``test_tpu_compile.py`` compiled
for the described v5e. Widths are the smallest the compiled kernels take
(heads of 64, 128 positions), so one build serves both."""

import contextlib

import jax
import jax.numpy as jnp
import optax
from jax.extend import core as jcore

PARTS = ("embed", "norm", "attn_proj", "attn_xla", "attn_layout", "mlp",
         "head")
EXPERT_SCOPES = ("mla_proj", "moe_route", "moe_experts")
KDA_SCOPES = ("kda_proj", "kda_conv", "kda_gate")
GDN_SCOPES = ("gdn_proj", "gdn_gate")
SELECT_SCOPES = ("index_proj",)
SEQ = 128


def _lm(model, tokens_to_loss):
    def loss_fn(params, batch):
        return tokens_to_loss(model, params, batch["tokens"])

    return loss_fn


def _gpt2(**kw):
    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel

    def build(use_flash):
        model = GPT2LMModel(GPT2Config.tiny(
            d_model=kw.get("d_model", 128), n_heads=2, max_len=SEQ,
            use_flash=use_flash,
        ))

        def loss(model, params, tokens):
            logits = model.apply({"params": params}, tokens[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens[:, 1:]
            ).mean()

        return model, _lm(model, loss), {"tokens": (SEQ + 1,)}

    return "GPT2LMModel", build


def _bert(num_labels):
    from horovod_tpu.models.bert import BertConfig, BertModel

    def build(use_flash):
        model = BertModel(
            BertConfig.tiny(d_model=128, n_heads=2, max_len=SEQ,
                            use_flash=use_flash),
            num_labels=num_labels,
        )

        def loss_fn(params, batch):
            out = model.apply(
                {"params": params}, batch["tokens"],
                token_types=batch["token_types"],
                attention_mask=batch.get("attention_mask"),
            )
            return optax.softmax_cross_entropy_with_integer_labels(
                out, batch["labels"]
            ).mean()

        batch = {"tokens": (SEQ,), "token_types": (SEQ,)}
        if num_labels is None:
            batch["labels"] = (SEQ,)
        else:  # the padded fine-tune: the mask sends attention down XLA
            batch.update(attention_mask=(SEQ,), labels=())
        return model, loss_fn, batch

    return "BertModel", build


def _latent_moe():
    from horovod_tpu.models.latent_moe import (
        LatentMoEConfig, LatentMoELM, lm_loss,
    )

    def build(use_flash):
        cfg = LatentMoEConfig.tiny(
            d_model=128, qk_nope_dim=64, qk_rope_dim=64, v_dim=64,
            use_flash=use_flash,
        )
        model = LatentMoELM(cfg)

        def loss(model, params, tokens):
            logits, mtp = model.apply({"params": params}, tokens[:, :-1])
            return lm_loss(logits, mtp, tokens, mtp_weight=cfg.mtp_weight)

        return model, _lm(model, loss), {"tokens": (SEQ + 2,)}

    return "LatentMoELM", build


def _window_moe(**settings):
    """``settings``: the block's static settings; with ``index_top_k`` the
    learned-sparse-attention layer, whose loss adds the index loss."""
    from horovod_tpu.models.window_moe import (
        WindowMoEConfig, WindowMoELM, lm_loss,
    )

    def build(use_flash):
        # heads of 128: a packed K/V block of ONE head fills the lanes
        cfg = WindowMoEConfig.tiny(
            d_model=128, n_heads=4, n_kv_heads=2, head_dim=128, window=64,
            use_flash=use_flash, **settings,
        )
        model = WindowMoELM(cfg)

        def loss(model, params, tokens):
            logits = model.apply({"params": params}, tokens[:, :-1])
            index_loss = 0.0
            if cfg.index_top_k:
                logits, index_loss = logits
            return lm_loss(logits, None, tokens, mtp_weight=0.0) + index_loss

        return model, _lm(model, loss), {"tokens": (SEQ + 1,)}

    return "WindowMoELM", build


def _linear_moe():
    from horovod_tpu.models.linear_moe import (
        LinearMoEConfig, LinearMoELM, lm_loss,
    )

    def build(use_flash):
        # KDA heads of 128: a head's tile fills the lanes; both kernel
        # families or neither
        cfg = LinearMoEConfig.tiny(
            d_model=128, kda_head_dim=128, qk_nope_dim=64, qk_rope_dim=64,
            v_dim=64, use_flash=use_flash, use_kernel=use_flash,
        )
        model = LinearMoELM(cfg)

        def loss(model, params, tokens):
            logits = model.apply({"params": params}, tokens[:, :-1])
            return lm_loss(logits, None, tokens, mtp_weight=0.0)

        return model, _lm(model, loss), {"tokens": (SEQ + 1,)}

    return "LinearMoELM", build


def _linear_dense():
    from horovod_tpu.models.linear_dense import (
        LinearDenseConfig, LinearDenseLM, lm_loss,
    )

    def build(use_flash):
        # the published head widths (96 / 192 off the lanes, 128 on them);
        # both kernel families or neither
        cfg = LinearDenseConfig.tiny(
            d_model=128, head_dim=128, gdn_key_dim=96, gdn_value_dim=192,
            use_flash=use_flash, use_kernel=use_flash,
        )
        model = LinearDenseLM(cfg)

        def loss(model, params, tokens):
            logits = model.apply({"params": params}, tokens[:, :-1])
            return lm_loss(logits, None, tokens, mtp_weight=0.0)

        return model, _lm(model, loss), {"tokens": (SEQ + 1,)}

    return "LinearDenseLM", build


# id -> (family, use_flash); the golden parameter paths are per family
CASES = {
    "gpt2-flash": ("gpt2", True),
    "gpt2-flash-headmajor": ("gpt2_d96", True),
    "gpt2-xla": ("gpt2", False),
    "bert-mlm-flash": ("bert_mlm", True),
    "bert-cls-padded": ("bert_cls", False),
    "latent-moe-flash": ("latent_moe", True),
    "latent-moe-xla": ("latent_moe", False),
    "window-moe-flash": ("window_moe", True),
    "window-moe-xla": ("window_moe", False),
    "select-moe-kernels": ("select_moe", True),
    "select-moe-xla": ("select_moe", False),
    "linear-moe-kernels": ("linear_moe", True),
    "linear-moe-xla": ("linear_moe", False),
    "linear-dense-kernels": ("linear_dense", True),
    "linear-dense-xla": ("linear_dense", False),
}
_FAMILIES = {
    "gpt2": lambda: _gpt2(),
    "gpt2_d96": lambda: _gpt2(d_model=192),  # heads of 96: off the lanes
    "bert_mlm": lambda: _bert(None),
    "bert_cls": lambda: _bert(2),
    "latent_moe": _latent_moe,
    "window_moe": _window_moe,
    "select_moe": lambda: _window_moe(
        n_layers=2, window_layout=(0,), rope_layout=(1,),
        router_input="ffn_norm", expert_activation="silu", qk_norm=True,
        index_top_k=32, index_heads=2, index_head_dim=64,
        index_blocks=(128, 128),
    ),
    "linear_moe": _linear_moe,
    "linear_dense": _linear_dense,
}


def build(case: str, n_batch: int):
    """``(top module's name, model, loss_fn, params, batch)``, the last two
    as shapes."""
    family, use_flash = CASES[case]
    top, make = _FAMILIES[family]()
    model, loss_fn, batch_shapes = make(use_flash)
    batch = {
        name: jax.ShapeDtypeStruct((n_batch,) + shape, jnp.int32)
        for name, shape in batch_shapes.items()
    }
    # parameters depend on neither the attention path nor the length
    init_model, _, _ = make(False)
    inputs = {k: jnp.zeros((1,) + v.shape[1:2], jnp.int32)
              for k, v in batch.items() if k != "labels"}
    tokens = inputs.pop("tokens")
    params = jax.eval_shape(
        lambda key: init_model.init(key, tokens, **inputs)["params"],
        jax.random.PRNGKey(0),
    )
    return top, model, loss_fn, params, batch


def param_paths(params) -> list:
    return sorted(
        "/".join(str(getattr(k, "key", k)) for k in path)
        + " " + "x".join(map(str, leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    )


def operations(jaxpr, prefix=""):
    """``(primitive, name stack, has an operand that is not a literal)`` of
    every equation, those of nested jaxprs under their caller's stack (as
    lowering composes them); a ``pallas_call`` is one operation."""
    for eqn in jaxpr.eqns:
        own = str(eqn.source_info.name_stack)
        stack = f"{prefix}/{own}" if prefix and own else prefix or own
        inner = [
            v.jaxpr if isinstance(v, jcore.ClosedJaxpr) else v
            for v in eqn.params.values()
            if isinstance(v, (jcore.Jaxpr, jcore.ClosedJaxpr))
        ]
        if inner and eqn.primitive.name != "pallas_call":
            for sub in inner:
                yield from operations(sub, stack)
        else:
            yield eqn.primitive.name, stack, any(
                not isinstance(v, jcore.Literal) for v in eqn.invars
            )


@contextlib.contextmanager
def parts_disabled():
    """``jax.named_scope`` opens nothing for a part's name: the model as it
    was before it named its parts. Every other scope (the phases, the
    expert model's, flax's module names) stays. Build inside it."""
    real = jax.named_scope
    jax.named_scope = lambda name: (
        contextlib.nullcontext() if name in PARTS else real(name)
    )
    # the flash entries' jitted calls keep their traces, name stacks and
    # all: neither build may be handed the other's
    jax.clear_caches()
    try:
        yield
    finally:
        jax.named_scope = real
        jax.clear_caches()
