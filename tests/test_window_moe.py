"""Window and full attention layers mixed, grouped query heads, top-k
routed experts held by share with the router ahead of the attention
(``models/window_moe.py``, ``parallel/ep.py``, the flash kernels' window
and query groups): the system against the benchmark's plain reference
(``benchmark/lib/plain_window_moe.py``, which shares no code with it) at
tiny sizes, seeded weights, float32. The tiny sequence is four windows
long (32 positions, window 8) and a K/V head serves three query heads.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import plain_window_moe as plain
from horovod_tpu.models import latent_moe, transformer
from horovod_tpu.models.window_moe import (
    GroupedAttention,
    WindowMoEBlock,
    WindowMoEConfig,
    WindowMoELM,
    band_mask,
    lm_loss,
)
from horovod_tpu.parallel import ep

SEQ = 32


def _tiny(**kw):
    kw.setdefault("use_flash", False)
    return WindowMoEConfig.tiny(dtype=jnp.float32, **kw)


def _sizes(cfg: WindowMoEConfig, **kw) -> plain.Sizes:
    return plain.Sizes(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, window=cfg.window,
        window_layout=cfg.window_layout, rope_layout=cfg.rope_layout,
        rope_theta=cfg.rope_theta, first_expert=cfg.first_expert,
        top_k=cfg.top_k, eps=cfg.eps, q_block=8, **kw,
    )


def _system_loss(cfg):
    model = WindowMoELM(cfg)

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, None, tokens, mtp_weight=0.0)

    return loss


def _tokens(cfg, seed, batch=2):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, SEQ + 1), 0, cfg.vocab_size
    )


def _params(cfg, seed=0, scale=5.0):
    """Seeded weights, the matrices scaled up so that the routing, the
    masks and the rotation all move the loss by more than rounding."""
    init = WindowMoELM(dataclasses.replace(cfg, use_flash=False))
    params = init.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return jax.tree.map(lambda x: x * scale if x.ndim > 1 else x, params)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(flash):
    """Through XLA attention and through the flash kernels (interpreted):
    window and full layers, rotary in three of four, 3:1 groups."""
    cfg = _tiny(use_flash=flash)
    params, tokens = _params(cfg), _tokens(cfg, 1)
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(_system_loss(cfg))(params, tokens)
        want, want_grads = jax.value_and_grad(
            lambda p: plain.loss(p, tokens, _sizes(cfg))
        )(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    leaves = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    assert len(leaves) == 4 * 10 + 3
    for (path, a), b in zip(leaves, jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-9,
            err_msg=jax.tree_util.keystr(path),
        )


def test_every_mechanism_moves_the_tiny_loss():
    """The comparison above would not see a mechanism that does nothing at
    the tiny size: the window, the rotation, the groups' sharing and the
    router's input each move the reference's loss."""
    cfg = _tiny()
    params, tokens = _params(cfg), _tokens(cfg, 1)
    z = _sizes(cfg)
    sound = float(plain.loss(params, tokens, z))
    for altered in (
        dataclasses.replace(z, window=SEQ),
        dataclasses.replace(z, rope_layout=(0,)),
        dataclasses.replace(z, window_layout=(1,)),
        dataclasses.replace(z, top_k=2),
    ):
        assert abs(float(plain.loss(params, tokens, altered)) - sound) > 1e-3


@pytest.mark.parametrize("windowed,rotate", [(True, True), (False, False)])
def test_flash_path_hands_the_kernels_the_projections_as_they_are(
    windowed, rotate
):
    """Packed ``bsm`` with ``n_kv_heads``: q at the query heads' width, K
    and V at the K/V heads', no operand at the query heads' width but q,
    and the window layer's kernels named apart from the full layer's."""
    cfg = _tiny(use_flash=True)
    attn = GroupedAttention(
        cfg, window=cfg.window if windowed else None, rotate=rotate
    )
    x = jnp.zeros((1, SEQ, cfg.d_model), jnp.float32)
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)["params"]

    def loss(params, x):
        return attn.apply({"params": params}, x).sum()

    from horovod_tpu.analysis.jaxpr_walk import _sub_jaxprs_generic

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in _sub_jaxprs_generic(eqn):
                yield from walk(sub)

    traced = jax.make_jaxpr(jax.grad(loss))(params, x)
    calls = {e.params["name"]: e for e in walk(traced.jaxpr)
             if e.primitive.name == "pallas_call"}
    suffix = "_window" if windowed else ""
    assert sorted(calls) == [f"hvd_flash_bwd_dkv{suffix}",
                             f"hvd_flash_bwd_dq{suffix}",
                             f"hvd_flash_fwd{suffix}"]
    wide, narrow = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    for name, call in calls.items():
        operands = [tuple(v.aval.shape) for v in call.invars
                    if v.aval.ndim == 3]
        assert operands.count((1, SEQ, narrow)) == 2, (name, operands)
        assert set(operands) == {(1, SEQ, wide), (1, SEQ, narrow)}
    assert [tuple(v.aval.shape)
            for v in calls[f"hvd_flash_bwd_dkv{suffix}"].outvars] == [
        (1, SEQ, narrow), (1, SEQ, narrow)
    ]
    # PR 41: a rotated layer's kernels rotate q themselves, so the q they
    # get is the projection's matmul output, and q's cotangent is dQ's
    # result as it leaves; the table is the one 2-D operand beside them
    forward = jax.make_jaxpr(loss)(params, x).jaxpr.eqns
    (entry,) = [e for e in forward if e.primitive.name == "custom_vjp_call"]
    (made_q,) = [e for e in forward if entry.invars[0] in e.outvars]
    assert made_q.primitive.name == "dot_general"
    for name, call in calls.items():
        tables = [tuple(v.aval.shape) for v in call.invars
                  if v.aval.ndim == 2 and v.aval.shape[0] == SEQ]
        turns = rotate and "dkv" not in name
        assert tables == ([(SEQ, 2 * cfg.head_dim)] if turns else []), name
    halves = [e for e in traced.jaxpr.eqns if e.outvars and tuple(
        e.outvars[0].aval.shape) == (1, SEQ, cfg.n_heads, cfg.head_dim // 2)]
    assert not halves, halves  # only k (n_kv_heads) is rotated outside


@pytest.mark.parametrize("windowed", [True, False])
def test_rotated_layer_through_the_kernels_equals_the_xla_path(windowed):
    """Output and every gradient of a rotated layer: the kernels rotating
    q (interpreter) against ``use_flash=False``'s ``rotary`` in XLA."""
    cfg = _tiny()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, cfg.d_model))
    w = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    window = cfg.window if windowed else None
    params = GroupedAttention(cfg, window=window, rotate=True).init(
        jax.random.PRNGKey(0), x
    )

    def run(use_flash):
        attn = GroupedAttention(
            dataclasses.replace(cfg, use_flash=use_flash), window=window,
            rotate=True,
        )

        def loss(p, x):
            out = attn.apply(p, x)
            return (out * w).sum(), out

        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(
                params, x
            )

    (got, got_dx), got_out = run(True)
    (want, want_dx), want_out = run(False)
    np.testing.assert_allclose(got_out, want_out, atol=2e-6)
    np.testing.assert_allclose(got_dx, want_dx, atol=2e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))


def test_band_mask_is_the_written_rule():
    valid = band_mask(6, 3)
    for i in range(6):
        for j in range(6):
            assert valid[i, j] == (0 <= i - j < 3)
    assert (band_mask(6, None) == np.tril(np.ones((6, 6), bool))).all()


def test_rotary_halves_pairs_column_i_with_i_plus_half():
    """``halves=True`` is ``x cos + rotate_half(x) sin``; the scores it
    gives depend on relative position alone; and the adjacent-pair form
    is the same rotation of a permuted head."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 2, 8))
    got = transformer.rotary(x, theta=100.0, halves=True)
    np.testing.assert_allclose(
        got, plain.rotary(x, 100.0), rtol=1e-6, atol=1e-6
    )
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    np.testing.assert_allclose(
        got, transformer.rotary(x[..., np.argsort(perm)], theta=100.0)[
            ..., perm],
        rtol=1e-6, atol=1e-6,
    )
    q = transformer.rotary(jnp.broadcast_to(x[:, :1], x.shape), theta=100.0,
                           halves=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, q)
    np.testing.assert_allclose(
        scores[0, 0, 1, 0], scores[0, 0, 4, 3], rtol=1e-5
    )
    assert latent_moe.rotary is transformer.rotary
    assert latent_moe.lm_loss is transformer.lm_loss is lm_loss


def _block_parts(cfg, seed=3):
    block = WindowMoEBlock(cfg, windowed=True, rotate=True)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, cfg.d_model))
    params = block.init(jax.random.PRNGKey(seed + 1), x)["params"]
    params = jax.tree.map(lambda p: p * 5 if p.ndim > 1 else p, params)
    return block, params, x


def test_shares_add_up_to_the_uncut_layer():
    """One layer, 16 experts over 4 chips: what the four shares' experts
    give, with what every chip computes alike (the attention and the
    residual) counted once, is the uncut reference's layer."""
    chips = 4
    whole = _tiny(n_experts_held=16)
    _, params, x = _block_parts(whole)
    z = dataclasses.replace(
        _sizes(whole), window_layout=(1,), rope_layout=(1,)
    )
    with jax.default_matmul_precision("highest"):
        uncut = plain.block(params, x, z, jnp.bool_(True), jnp.bool_(True))
        # every chip's h' = h + attention: the layer with no expert held
        alike = plain.block(
            {**params, "experts_down": jnp.zeros_like(params["experts_down"])},
            x, z, jnp.bool_(True), jnp.bool_(True),
        )
        total = alike
        for chip in range(chips):
            cfg = dataclasses.replace(
                whole, n_experts_held=4, first_expert=4 * chip
            )
            held = slice(4 * chip, 4 * chip + 4)
            share = {**params, **{
                name: params[name][held] for name in
                ("experts_gate", "experts_up", "experts_down")
            }}
            out = WindowMoEBlock(cfg, windowed=True, rotate=True).apply(
                {"params": share}, x
            )
            total = total + (out - alike)
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(uncut - alike))) > 1e-2


def test_dropless_under_skew():
    """Every token the same, so every token chooses the same three
    experts, all held here: each sees all ``T`` tokens and none is
    dropped (the plain reference has no capacity to exceed)."""
    cfg = _tiny(n_experts_held=16)
    block, params, x = _block_parts(cfg)
    x = jnp.broadcast_to(x[:1, :1], x.shape)
    seen = []
    real = ep.local_experts

    def record(tokens, chosen, weights, *stacks, **kw):
        seen.append(chosen)
        return real(tokens, chosen, weights, *stacks, **kw)

    ep.local_experts = record
    try:
        with jax.default_matmul_precision("highest"):
            got = block.apply({"params": params}, x)
    finally:
        ep.local_experts = real
    assert (np.asarray(seen[0]) == np.asarray(seen[0])[0]).all()
    z = dataclasses.replace(
        _sizes(cfg), window_layout=(1,), rope_layout=(1,)
    )
    with jax.default_matmul_precision("highest"):
        want = plain.block(params, x, z, jnp.bool_(True), jnp.bool_(True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_static_work_is_one_jaxpr_for_two_seeds():
    cfg = _tiny(use_flash=True)
    loss = _system_loss(cfg)
    traced = [
        str(jax.make_jaxpr(jax.grad(loss))(
            _params(cfg, seed), _tokens(cfg, seed)
        )) for seed in (0, 1)
    ]
    assert traced[0] == traced[1]


def test_softmax_of_the_chosen_is_the_softmax_over_all_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
    router = jax.random.normal(jax.random.PRNGKey(6), (32, 16))
    chosen, weights = ep.topk_route(
        x, router, None, top_k=6, scoring="softmax"
    )
    probs = jax.nn.softmax(
        jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST), axis=-1
    )
    picked, want = jax.lax.top_k(probs, 6)
    assert (np.asarray(chosen) == np.asarray(want)).all()
    assert chosen.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True), rtol=1e-5
    )
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    scaled = ep.topk_route(x, router, None, top_k=6, scoring="softmax",
                           scale=2.5)[1]
    np.testing.assert_allclose(scaled, 2.5 * weights, rtol=1e-6)


def test_chosen_experts_do_not_move_with_the_attentions_weights():
    """The router reads the layer's input: other attention weights change
    the layer's output and leave every token's experts where they were."""
    cfg = _tiny()
    block, params, x = _block_parts(cfg)
    other = {**params, "attn": jax.tree.map(lambda w: -2.0 * w,
                                            params["attn"])}
    seen, real = [], ep.local_experts

    def record(tokens, chosen, weights, *stacks, **kw):
        seen.append((chosen, weights))
        return real(tokens, chosen, weights, *stacks, **kw)

    ep.local_experts = record
    try:
        outs = [block.apply({"params": p}, x) for p in (params, other)]
    finally:
        ep.local_experts = real
    assert (np.asarray(seen[0][0]) == np.asarray(seen[1][0])).all()
    assert (np.asarray(seen[0][1]) == np.asarray(seen[1][1])).all()
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 1e-2


def test_default_scoring_and_activation_leave_the_latent_models_jaxpr():
    """``scoring="sigmoid"`` and ``activation="silu"`` are the defaults
    and trace what the two functions traced before they took the
    arguments (the sigmoid router written out as it stood)."""
    x = jax.ShapeDtypeStruct((48, 32), jnp.bfloat16)
    router = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    bias = jax.ShapeDtypeStruct((16,), jnp.float32)

    def as_it_stood(x, router_kernel, score_bias, *, top_k, scale):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ))
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(score_bias.astype(jnp.float32)),
            top_k,
        )
        picked = jnp.einsum(
            "tke,te->tk",
            jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32),
            scores, precision=jax.lax.Precision.HIGHEST,
        )
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
        return chosen.astype(jnp.int32), weights

    how = dict(top_k=4, scale=2.5)
    stood = str(jax.make_jaxpr(
        lambda *a: as_it_stood(*a, **how))(x, router, bias))
    assert stood == str(jax.make_jaxpr(
        lambda *a: ep.topk_route(*a, **how))(x, router, bias))
    assert stood == str(jax.make_jaxpr(
        lambda *a: ep.topk_route(*a, scoring="sigmoid", **how)
    )(x, router, bias))

    stacks = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((4, 32, 24), (4, 32, 24), (4, 24, 32))]
    chosen = jax.ShapeDtypeStruct((48, 4), jnp.int32)
    weights = jax.ShapeDtypeStruct((48, 4), jnp.float32)
    share = dict(first_expert=0, n_experts=16)
    default, silu, relu = (
        str(jax.make_jaxpr(jax.grad(
            lambda x, *rest: ep.local_experts(
                x, *rest, **share, **kw
            ).astype(jnp.float32).sum()
        ))(x, chosen, weights, *stacks))
        for kw in ({}, {"activation": "silu"}, {"activation": "relu"})
    )
    assert default == silu and "logistic" in silu
    assert relu != silu and "logistic" not in relu and "max" in relu


@pytest.mark.parametrize(
    "call,match",
    [
        (lambda: ep.topk_route(jnp.zeros((4, 8)), jnp.zeros((8, 4)), None,
                               top_k=2, scoring="tanh"), "scoring"),
        (lambda: ep.local_experts(
            jnp.zeros((4, 8)), jnp.zeros((4, 2), jnp.int32),
            jnp.zeros((4, 2)), jnp.zeros((2, 8, 4)), jnp.zeros((2, 8, 4)),
            jnp.zeros((2, 4, 8)), first_expert=0, n_experts=4,
            activation="gelu"), "activation"),
    ],
    ids=["scoring", "activation"],
)
def test_unknown_scoring_or_activation_is_refused(call, match):
    with pytest.raises(ValueError, match=match):
        call()


# -- learned sparse attention: the block's static settings ------------------
# (``index_top_k`` with the router on ``RMSNorm2(h')``, SiLU experts and
# head-wise q / k norms) against ``benchmark/lib/plain_select_moe.py``


def _select_cfg(**kw):
    base = dict(
        window_layout=(0,), rope_layout=(1,), n_layers=2,
        router_input="ffn_norm", expert_activation="silu", qk_norm=True,
        index_top_k=8, index_heads=4, index_head_dim=8,
        index_blocks=(16, 16),
    )
    base.update(kw)
    return _tiny(**base)


def _select_sizes(cfg, **kw):
    from benchmark.lib import plain_select_moe

    return plain_select_moe.Sizes(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        index_heads=cfg.index_heads, index_head_dim=cfg.index_head_dim,
        index_top_k=cfg.index_top_k, rope_theta=cfg.rope_theta,
        first_expert=cfg.first_expert, top_k=cfg.top_k, eps=cfg.eps,
        q_block=8, **kw,
    )


def _select_losses(cfg):
    """``params, tokens -> (cross entropy, index loss)`` of the program."""
    model = WindowMoELM(cfg)

    def parts(params, tokens):
        logits, index_loss = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, None, tokens, mtp_weight=0.0), index_loss

    return parts


def _leaves(tree):
    return {
        "/".join(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_select_model_matches_its_plain_reference_leaf_by_leaf(flash):
    """Loss, index loss and every leaf's gradient, through XLA and through
    the select, masked flash and index-loss kernels (interpreted): 32
    positions of which a query keeps 8, 3:1 groups, two layers."""
    from benchmark.lib import plain_select_moe

    cfg = _select_cfg(use_flash=flash)
    params, tokens = _params(cfg, scale=3.0), _tokens(cfg, 2)
    parts = _select_losses(cfg)
    z = _select_sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got_ce, got_index = parts(params, tokens)
        want_ce, want_index = plain_select_moe.loss_parts(params, tokens, z)
        got_grads = jax.grad(lambda p: sum(parts(p, tokens)))(params)
        want_grads = jax.grad(
            lambda p: plain_select_moe.loss(p, tokens, z)
        )(params)
    assert float(want_index) > 1e-2
    assert float(got_ce) == pytest.approx(float(want_ce), rel=1e-5)
    assert float(got_index) == pytest.approx(float(want_index), rel=1e-4)
    got, want = _leaves(got_grads), _leaves(want_grads)
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_allclose(
            got[name], want[name], rtol=2e-3,
            atol=2e-5 * float(jnp.abs(want[name]).max()) + 1e-9,
            err_msg=name,
        )
    # selection changes the result: the reference that keeps everything
    everything = plain_select_moe.loss_parts(
        params, tokens, _select_sizes(cfg, departure="keep_all")
    )
    assert abs(float(everything[0]) - float(want_ce)) > 1e-4


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "kernels"])
def test_the_two_stop_gradients(flash):
    """The indexer's leaves are unmoved by the cross entropy and every
    other leaf by the index loss."""
    cfg = _select_cfg(use_flash=flash)
    params, tokens = _params(cfg, scale=3.0), _tokens(cfg, 3)
    parts = _select_losses(cfg)
    from_ce = _leaves(jax.grad(lambda p: parts(p, tokens)[0])(params))
    from_index = _leaves(jax.grad(lambda p: parts(p, tokens)[1])(params))
    indexer = [n for n in from_ce if "/index_" in n]
    assert len(indexer) == cfg.n_layers * 5  # q, k, its norm's two, w
    for name in from_ce:
        ce, index = (float(jnp.abs(g[name]).max())
                     for g in (from_ce, from_index))
        if name in indexer:
            assert ce == 0.0 and index > 0.0, name
        else:
            assert index == 0.0 and ce > 0.0, name


@pytest.mark.parametrize("rope_layout", [(1,), (0, 1)],
                         ids=["rotated", "nope-then-rotated"])
def test_select_model_through_the_kernels_equals_the_xla_path(rope_layout):
    """Cross entropy, index loss and every leaf's gradient (``q_norm/scale``
    and the indexer's among them): the flash path, whose kernels norm and
    rotate q and hand the index loss the q their scores saw (interpreted),
    against ``use_flash=False``'s ``RMSNorm`` and ``rotary`` in XLA; in the
    second case layer 0 is not rotated, so its kernels norm without a
    turn."""
    cfg = _select_cfg(rope_layout=rope_layout)
    params, tokens = _params(cfg, scale=3.0), _tokens(cfg, 4)

    def run(flash):
        parts = _select_losses(dataclasses.replace(cfg, use_flash=flash))
        with jax.default_matmul_precision("highest"):
            return parts(params, tokens), _leaves(
                jax.grad(lambda p: sum(parts(p, tokens)))(params)
            )

    (got_ce, got_index), got = run(True)
    (want_ce, want_index), want = run(False)
    assert float(got_ce) == pytest.approx(float(want_ce), rel=1e-5)
    assert float(got_index) == pytest.approx(float(want_index), rel=1e-4)
    assert sorted(got) == sorted(want)
    assert sum("q_norm/scale" in name for name in want) == cfg.n_layers
    for name in want:
        assert float(jnp.abs(want[name]).max()) > 0.0, name
        np.testing.assert_allclose(
            got[name], want[name], rtol=2e-3,
            atol=2e-5 * float(jnp.abs(want[name]).max()) + 1e-9,
            err_msg=name,
        )


def test_flash_path_norms_q_in_the_kernels_and_rotates_it_nowhere_else():
    """A select layer with q / k norms on the flash path: the forward
    kernel's q is ``dense("q")``'s matmul output and its scale the
    ``q_norm/scale`` leaf as ``[1, d]``; the index-loss kernel reads the
    forward's second array, the q the scores saw, so XLA neither norms nor
    rotates anything at the query heads' width; the scale's gradient is
    the sum of dQ's float32 rows; and the parameter tree is the XLA
    path's."""
    from horovod_tpu.analysis.jaxpr_walk import _sub_jaxprs_generic

    cfg = _select_cfg(use_flash=True, n_layers=1)
    attn = GroupedAttention(cfg, rotate=True)
    x = jnp.zeros((1, SEQ, cfg.d_model), jnp.float32)
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x)["params"]
    off_flash = jax.eval_shape(
        GroupedAttention(
            dataclasses.replace(cfg, use_flash=False), rotate=True
        ).init, jax.random.PRNGKey(0), x,
    )["params"]
    assert jax.tree.structure(params) == jax.tree.structure(off_flash)
    assert _leaves(params)["q_norm/scale"].shape == (cfg.head_dim,)

    def loss(params, x):
        out, index_loss = attn.apply({"params": params}, x)
        return out.sum() + index_loss

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in _sub_jaxprs_generic(eqn):
                yield from walk(sub)

    traced = jax.make_jaxpr(jax.grad(loss))(params, x)
    calls = {e.params["name"]: e for e in walk(traced.jaxpr)
             if e.primitive.name == "pallas_call"}
    fwd, dq = calls["hvd_flash_fwd_select"], calls["hvd_flash_bwd_dq_select"]
    wide = (1, SEQ, cfg.n_heads * cfg.head_dim)
    made = {v: e for e in traced.jaxpr.eqns for v in e.outvars}
    (raw_q,) = [v for v in fwd.invars if tuple(v.aval.shape) == wide]
    assert made[raw_q].primitive.name == "dot_general"
    assert raw_q in dq.invars
    q_seen = fwd.outvars[-1]
    assert tuple(q_seen.aval.shape) == wide
    seen, readers = {q_seen}, []  # through the stop_gradients, as it is
    for eqn in traced.jaxpr.eqns:
        if any(type(v).__name__ == "Var" and v in seen for v in eqn.invars):
            if eqn.primitive.name == "stop_gradient":
                seen.update(eqn.outvars)
            else:
                readers.append(eqn.params["name"])
    assert sorted(set(readers)) == [
        "hvd_dsa_kl", "hvd_flash_bwd_dkv_select", "hvd_flash_bwd_dq_select"
    ]
    # nothing at the query heads' width is normed or rotated by XLA: no
    # array [.., heads, d] or [.., heads, d / 2] of q's exists
    h, d = cfg.n_heads, cfg.head_dim
    by_head = [e for e in walk(traced.jaxpr) if e.primitive.name not in (
        "pallas_call", "pjit") and any(
        tuple(v.aval.shape) in ((1, SEQ, h, d), (1, SEQ, h, d // 2))
        and v.aval.dtype == jnp.float32 for v in e.outvars)]
    # (``rowsum(g * out)`` reads out by head: the flash entry's own glue)
    assert all("attn_layout" in str(e.source_info.name_stack)
               for e in by_head), by_head
    (rows,) = [v for v in dq.outvars if v.aval.ndim == 5]
    assert rows.aval.dtype == jnp.float32 and rows.aval.shape[-1] == d
    (summed,) = [e for e in traced.jaxpr.eqns if rows in e.invars]
    assert summed.primitive.name == "reduce_sum"


def test_select_shares_add_up_to_the_uncut_layer():
    """One layer, 16 experts over 4 chips, selection on: what the four
    shares' experts give, with what every chip computes alike (attention
    over the kept set, the residual) counted once, is the uncut
    reference's layer, and every share reports the layer's index loss."""
    from benchmark.lib import plain_select_moe

    chips = 4
    whole = _select_cfg(n_experts_held=16)
    block = WindowMoEBlock(whole, rotate=True)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, whole.d_model))
    params = block.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree.map(lambda p: p * 3 if p.ndim > 1 else p, params)
    z = _select_sizes(whole)
    nothing_held = {
        **params, "experts_down": jnp.zeros_like(params["experts_down"])
    }
    with jax.default_matmul_precision("highest"):
        uncut, index_loss = plain_select_moe.block(params, x, z)
        alike, _ = plain_select_moe.block(nothing_held, x, z)
        total = alike
        for chip in range(chips):
            cfg = dataclasses.replace(
                whole, n_experts_held=4, first_expert=4 * chip
            )
            held = slice(4 * chip, 4 * chip + 4)
            share = {**params, **{
                name: params[name][held] for name in
                ("experts_gate", "experts_up", "experts_down")
            }}
            out, share_loss = WindowMoEBlock(cfg, rotate=True).apply(
                {"params": share}, x
            )
            total = total + (out - alike)
            assert float(share_loss) == pytest.approx(float(index_loss),
                                                      rel=1e-4)
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(uncut - alike))) > 1e-2


def test_router_input_and_activation_are_checked():
    with pytest.raises(ValueError, match="router_input"):
        WindowMoEBlock(_tiny(router_input="embedding")).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 48))
        )
    with pytest.raises(ValueError, match="activation"):
        WindowMoEBlock(_tiny(expert_activation="gelu")).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 48))
        )


@pytest.mark.parametrize(
    "flash,sha",
    [(False,
      "bab2287090853cedf323e51f3fca44ff6315ad86747b11c27d4642873a0d3c79"),
     (True,
      "9f6c0da5af9607843db603f2db47561797636599accec426abe3e1ba06414213")],
    ids=["xla", "kernels"],
)
def test_default_settings_trace_what_the_block_traced(flash, sha):
    """``WindowMoEConfig``'s defaults (router on ``RMSNorm1(h)``, ReLU
    experts, no q / k norm, no indexer) trace, gradient and all, the text
    the block traced before it took the settings, the flash kernels without
    ``keep=`` theirs: the sha256 of ``str(jax.make_jaxpr(...))`` as the
    parent of the settings' PR printed it (jax 0.9.0). An edit that means
    to change the default block's trace records the new one here (PR 49:
    the kernels' text, whose backward makes its row statistic in dQ; the
    XLA path's is the parent's)."""
    import hashlib

    cfg = WindowMoEConfig.tiny(use_flash=flash)
    model = WindowMoELM(cfg)
    shapes = jax.eval_shape(
        lambda: WindowMoELM(WindowMoEConfig.tiny(use_flash=False)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    )
    tokens = jax.ShapeDtypeStruct((2, SEQ + 1), jnp.int32)

    def loss(params, tokens):
        return lm_loss(model.apply({"params": params}, tokens[:, :-1]), None,
                       tokens, mtp_weight=0.0)

    text = str(jax.make_jaxpr(jax.grad(loss))(shapes, tokens))
    assert hashlib.sha256(text.encode()).hexdigest() == sha
