"""Gated DeltaNet layers beside a full-attention layer under a dense FFN,
the mixers' heads held by share (``models/linear_dense.py``, the
scalar-gate form of ``ops/kda_kernels.py``, ``window_moe.GroupedAttention``'s
whole-projection norm): the system against the benchmark's plain reference
(``benchmark/lib/plain_linear_dense.py``, which runs the recurrence a
position at a time and shares no code with it) at tiny sizes with ``d_k !=
d_v`` (12 / 24), seeded weights, float32. ``A_log``, ``dt_bias`` and the
taps are drawn as the configuration's ``assumed`` says, so the decay is not
degenerate.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.lib import plain_linear_dense as plain
from horovod_tpu.models.linear_dense import (
    FULL, LINEAR, GatedDeltaNet, LinearDenseConfig, LinearDenseLM, lm_loss,
)
from horovod_tpu.models.transformer import RMSNorm
from horovod_tpu.models.window_moe import (
    GroupedAttention, ProjectionNorm, WindowMoEConfig,
)

SEQ = 32


def _tiny(**kw):
    kw.setdefault("use_flash", False)
    kw.setdefault("use_kernel", False)
    return LinearDenseConfig.tiny(dtype=jnp.float32, **kw)


def _sizes(cfg: LinearDenseConfig, **kw) -> plain.Sizes:
    return plain.Sizes(
        layer_types=cfg.layer_types[:cfg.n_layers], heads=cfg.heads_held,
        head_dim=cfg.head_dim, key_dim=cfg.gdn_key_dim,
        value_dim=cfg.gdn_value_dim, eps=cfg.eps, scan_group=8, q_block=8,
        **kw,
    )


def _loss(cfg):
    model = LinearDenseLM(cfg)

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, None, tokens, mtp_weight=0.0)

    return loss


def _tokens(cfg, seed, batch=2):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, SEQ + 1), 0, cfg.vocab_size
    )


def _params(cfg, seed=0, scale=5.0):
    """Seeded weights, the matrices scaled up so that the mixers and the
    gates move the loss by more than rounding; the decay's parameters and
    the taps stay as drawn."""
    init = LinearDenseLM(
        dataclasses.replace(cfg, use_flash=False, use_kernel=False)
    )
    params = init.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim < 2 or "conv" in str(path[-1])
        else x * scale, params,
    )


def _assert_trees_close(got, want, tol):
    got, want = (
        dict(jax.tree_util.tree_flatten_with_path(t)[0]) for t in (got, want)
    )
    assert got.keys() == want.keys()
    for path, w in want.items():
        scale = float(jnp.abs(w).max())
        assert scale > 0, f"reference gradient of {path} is all zero"
        np.testing.assert_allclose(
            got[path], w, atol=tol * scale, rtol=0,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(kernels):
    """Through the recurrence and XLA attention, and through both kernel
    families (interpreted; the scalar-gate kernels with their door and
    exit)."""
    cfg = _tiny(use_flash=kernels, use_kernel=kernels)
    params, tokens = _params(cfg), _tokens(cfg, 1)
    z = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(_loss(cfg)))(
            params, tokens
        )
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: plain.loss(p, tokens, z)
        ))(params)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    _assert_trees_close(got_grads, want_grads, 1e-4)
    mixer = got_grads["block_1"]["attn"]
    for leaf in ("A_log", "dt_bias", "conv_q", "conv_k", "conv_v", "a", "b",
                 "o_norm"):
        assert float(jnp.abs(mixer[leaf]).max()) > 0, leaf
    full = got_grads["block_3"]["attn"]
    assert float(jnp.abs(full["q_norm"]["scale"]).max()) > 0


def test_three_updates_follow_the_plain_reference():
    """AdamW on both sides from the same weights: the loss at each of three
    steps, the system through its kernels (interpreted)."""
    cfg = _tiny(use_flash=True, use_kernel=True)
    z, optimizer = _sizes(cfg), optax.adamw(3e-3)
    system, reference = _loss(cfg), lambda p, t: plain.loss(p, t, z)
    losses = {}
    with jax.default_matmul_precision("highest"):
        for name, fn in (("system", system), ("reference", reference)):
            params = _params(cfg)
            state = optimizer.init(params)
            step = jax.jit(jax.value_and_grad(fn))
            losses[name] = []
            for i in range(3):
                loss, grads = step(params, _tokens(cfg, 10 + i))
                updates, state = optimizer.update(grads, state, params)
                params = optax.apply_updates(params, updates)
                losses[name].append(float(loss))
    np.testing.assert_allclose(losses["system"], losses["reference"],
                               rtol=1e-5)
    assert losses["system"][0] != losses["system"][2]


@pytest.mark.parametrize(
    "departure", ["no_decay", "beta_unscaled", "no_conv", "no_qk_norm"]
)
def test_each_departure_of_the_reference_moves_the_loss(departure):
    """The controls the benchmark runs at the published widths
    (``families/linear_dense_lm.controls``) are real departures."""
    cfg = _tiny()
    params, tokens = _params(cfg), _tokens(cfg, 2)
    with jax.default_matmul_precision("highest"):
        sound, wrong = jax.jit(lambda p: (
            plain.loss(p, tokens, _sizes(cfg)),
            plain.loss(p, tokens, _sizes(cfg, departure=departure)),
        ))(params)
    assert abs(float(wrong - sound)) > 1e-4 * float(sound)


def test_layout_is_data_and_the_block_norms_its_sub_layers_output():
    published = LinearDenseConfig()
    assert [published.mixer(i) for i in range(4)] == [LINEAR] * 3 + [FULL]
    assert published.layer_types.count(LINEAR) == 24
    cfg = _tiny()
    params = _params(cfg)
    for i in range(cfg.n_layers):
        attn = params[f"block_{i}"]["attn"]
        assert ("A_log" in attn) == (i != 3) and ("q_norm" in attn) == (i == 3)
    h, dk, dv = cfg.heads_held, cfg.gdn_key_dim, cfg.gdn_value_dim
    linear = params["block_0"]["attn"]
    assert linear["q"]["kernel"].shape == (cfg.d_model, h * dk)
    assert linear["z"]["kernel"].shape == (cfg.d_model, h * dv)
    assert linear["a"].shape == linear["b"].shape == (cfg.d_model, h)
    assert linear["A_log"].shape == linear["dt_bias"].shape == (h,)
    assert linear["conv_v"].shape == (cfg.conv_size, h * dv)
    assert linear["o_norm"].shape == (dv,)
    # the whole held projection has ONE norm, a scale a column
    full = params["block_3"]["attn"]
    assert full["q_norm"]["scale"].shape == (h * cfg.head_dim,)
    # another layout, the same code: a full layer first
    other = _tiny(layer_types=(FULL, LINEAR), n_layers=2)
    tree, tokens = _params(other), _tokens(other, 3)
    assert "q_norm" in tree["block_0"]["attn"]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            _loss(other)(tree, tokens),
            plain.loss(tree, tokens, _sizes(other)), rtol=2e-6,
        )
    with pytest.raises(ValueError, match="layer_types"):
        _tiny(layer_types=("sliding",), n_layers=1).mixer(0)


def _slice_heads(tree, share: int, held: int, widths: dict):
    """Share ``share``'s part of an uncut mixer's parameters: ``held``
    heads' columns of the input projections, taps and norm scales, their
    rows of the output projection."""
    def cut(path, leaf):
        name = [getattr(p, "key", None) for p in path]
        top = name[0]
        if top in ("o_norm",):
            return leaf
        width = widths[top] * held
        first = share * width
        if top == "o":  # rows
            return leaf[first:first + width]
        if leaf.ndim == 1:
            return leaf[first:first + width]
        return leaf[..., first:first + width]

    return jax.tree_util.tree_map_with_path(cut, tree)


@pytest.mark.parametrize("mixer", [LINEAR, FULL])
def test_two_head_shares_under_one_axis_sum_to_the_uncut_layer(mixer):
    """A 4-head mixer cut two ways, 2 heads a share, the shares laid along
    one named axis (``vmap``): what each share returns, summed ONCE by the
    layer's own ``psum``, is the uncut layer's output; for the full layer
    the projection norm's statistic is the whole projection's too."""
    whole = _tiny(n_heads=4, heads_held=4)
    share = dataclasses.replace(whole, heads_held=2, axis_name="heads")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, whole.d_model))
    if mixer == LINEAR:
        layer = lambda cfg: GatedDeltaNet(cfg)  # noqa: E731
        dk, dv = whole.gdn_key_dim, whole.gdn_value_dim
        widths = dict(q=dk, k=dk, v=dv, z=dv, o=dv, a=1, b=1, A_log=1,
                      dt_bias=1, conv_q=dk, conv_k=dk, conv_v=dv)
    else:
        layer = lambda cfg: GroupedAttention(  # noqa: E731
            cfg.attention(), qk_norm_over="projection",
            axis_name=cfg.axis_name,
        )
        d = whole.head_dim
        widths = dict(q=d, k=d, v=d, o=d, q_norm=d, k_norm=d)
    params = layer(whole).init(jax.random.PRNGKey(4), x)["params"]
    params = jax.tree.map(
        lambda p: p * 4.0 if p.ndim == 2 and p.shape[0] > 8 else p, params
    )
    if mixer == FULL:  # scales that differ a column
        for n in ("q_norm", "k_norm"):
            params[n]["scale"] = 1.0 + 0.1 * jnp.arange(
                params[n]["scale"].size, dtype=jnp.float32
            ) / params[n]["scale"].size
    want = layer(whole).apply({"params": params}, x)
    shares = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *(_slice_heads(params, i, 2, widths) for i in range(2)),
    )
    got = jax.vmap(
        lambda p: layer(share).apply({"params": p}, x), axis_name="heads",
    )(shares)
    # every share holds the sum already
    np.testing.assert_allclose(got[0], got[1], atol=1e-6)
    assert float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # with no axis a share is alone: its own heads' part, nothing exchanged
    alone = layer(dataclasses.replace(share, axis_name=None)).apply(
        {"params": jax.tree.map(lambda p: p[0], shares)}, x
    )
    assert float(jnp.abs(alone - want).max()) > 1e-3


def test_projection_norm_is_rms_over_the_whole_projection():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 24))
    whole = ProjectionNorm(1e-6, jnp.float32)
    params = whole.init(jax.random.PRNGKey(1), x)
    np.testing.assert_allclose(
        whole.apply(params, x),
        RMSNorm(1e-6, jnp.float32).apply(params, x), atol=1e-6,
    )


def test_grouped_attention_defaults_are_the_head_wise_settings():
    """The new settings' defaults are what the block was: a head-wise norm
    where ``qk_norm`` is on, no axis, the same parameter tree and jaxpr as a
    call that names neither."""
    cfg = WindowMoEConfig.tiny(use_flash=False, qk_norm=True)
    x = jax.ShapeDtypeStruct((1, 16, cfg.d_model), jnp.float32)
    plain_layer = GroupedAttention(cfg)
    named = GroupedAttention(cfg, qk_norm_over="head", axis_name=None)
    shapes = jax.eval_shape(
        lambda x: plain_layer.init(jax.random.PRNGKey(0), x), x
    )
    assert shapes["params"]["q_norm"]["scale"].shape == (cfg.head_dim,)
    texts = [
        str(jax.make_jaxpr(lambda p, x: m.apply(p, x))(shapes, x))
        for m in (plain_layer, named)
    ]
    assert texts[0] == texts[1] and "psum" not in texts[0]


def test_parameters_at_the_published_sizes_are_the_configurations():
    """766.2 M at 15 heads held, one period of four layers, an eighth of
    the vocabulary: the count the configuration file states."""
    cfg = LinearDenseConfig(
        vocab_size=12544, n_layers=4, heads_held=15, use_flash=False,
        use_kernel=False,
    )
    shapes = jax.eval_shape(
        lambda: LinearDenseLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    )
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["block_0"]["attn"]) == 44_375_262
    assert count(shapes["block_3"]["attn"]) == 29_495_040
    assert count(shapes["block_0"]["ffn"]) == 126_812_160
    assert count(shapes) == 766_241_946
    with pytest.raises(ValueError, match="heads"):
        jax.eval_shape(lambda: LinearDenseLM(
            dataclasses.replace(cfg, first_head=16)
        ).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
