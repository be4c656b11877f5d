"""Latent attention, top-k routed experts held by share, multi-token
prediction (``models/latent_moe.py``, ``parallel/ep.py``): the system
against the benchmark's plain reference (``benchmark/lib/
plain_latent_moe.py``, which shares no code with it) at tiny sizes, seeded
weights, float32.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import plain_latent_moe as plain
from horovod_tpu.analysis.jaxpr_walk import _sub_jaxprs_generic
from horovod_tpu.models.latent_moe import (
    LatentAttention,
    LatentMoEConfig,
    LatentMoELM,
    RoutedExperts,
    lm_loss,
    rotary,
)
from horovod_tpu.obs import registry
from horovod_tpu.parallel import ep


def _tiny(**kw):
    return LatentMoEConfig.tiny(dtype=jnp.float32, use_flash=False, **kw)


def _sizes(cfg: LatentMoEConfig) -> plain.Sizes:
    return plain.Sizes(
        n_layers=cfg.n_layers, n_dense_layers=cfg.n_dense_layers,
        n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
        v_dim=cfg.v_dim, rope_theta=cfg.rope_theta,
        first_expert=cfg.first_expert, top_k=cfg.top_k,
        routed_scale=cfg.routed_scale, n_mtp=cfg.n_mtp,
        mtp_weight=cfg.mtp_weight, eps=cfg.eps, head_group=1,
    )


def _system_loss(cfg):
    model = LatentMoELM(cfg)

    def loss(params, tokens):
        logits, mtp_logits = model.apply({"params": params}, tokens[:, :-1])
        return lm_loss(logits, mtp_logits, tokens, mtp_weight=cfg.mtp_weight)

    return model, loss


def _tokens(cfg, seed, batch=2, seq=24):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1 + cfg.n_mtp), 0,
        cfg.vocab_size,
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


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
@pytest.mark.parametrize("n_mtp", [1, 0], ids=["mtp", "no-mtp"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(n_mtp, flash):
    """Loss and the gradient of every parameter, with and without the
    multi-token term; through XLA attention and through the flash kernels
    (interpreter) at q / k heads of 24 and v heads of 16."""
    cfg = _tiny(n_mtp=n_mtp, first_expert=8)
    cfg = dataclasses.replace(cfg, use_flash=flash)
    model, loss = _system_loss(cfg)
    tokens = _tokens(cfg, 1)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    assert ("mtp_block" in params) == bool(n_mtp)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss))(params, tokens)
    want = jax.jit(jax.value_and_grad(
        lambda p, t: plain.loss(p, t, _sizes(cfg))
    ))(params, tokens)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    _assert_trees_close(got[1], want[1], 2e-4)


def test_flash_path_hands_the_kernels_kv_b_output_and_the_one_rotary_key():
    """Traced at the cell's widths (32 heads, 128 + 64 / 128): the flash
    path's kernels get ``kv_b``'s matmul output itself and the
    ``[B, S, 64]`` rotary key; since PR 41 q is ``q_b``'s matmul output
    itself too (the kernels rotate it: no ``[B, S, H, 192]`` concatenation
    is left, nothing is reshaped to pairs of 32 and nothing of q's is
    raised to float32), and nothing broadcasts the rotary key to the heads.
    The XLA path still builds q and K. The kernels are counted 3 a block,
    and the two that turn (forward, dQ) once each."""
    b, s, h, n, r, v = 2, 4096, 32, 128, 64, 128
    x = jax.ShapeDtypeStruct((b, s, 2048), jnp.bfloat16)
    counter = registry.always().counter("flash.calls.latent_kv")
    turning = registry.always().counter("flash.calls.rotary_q")

    params = jax.eval_shape(
        LatentAttention(LatentMoEConfig(use_flash=False)).init,
        jax.random.PRNGKey(0), x,
    )

    def eqns_of(use_flash, grad=False):
        attn = LatentAttention(LatentMoEConfig(use_flash=use_flash))
        fn = lambda p, x: attn.apply(p, x).astype(jnp.float32).sum()  # noqa: E731
        traced = jax.make_jaxpr(jax.grad(fn) if grad else fn)(params, x)
        return traced.jaxpr.eqns

    def built_keys(eqns):
        return (
            [e for e in eqns if e.primitive.name == "concatenate"
             and e.outvars[0].aval.shape == (b, s, h, n + r)],
            [e for e in eqns if e.primitive.name == "broadcast_in_dim"
             and e.outvars[0].aval.shape == (b, s, h, r)],
        )

    before, turned_before = counter.get(), turning.get()
    eqns = eqns_of(True)
    assert counter.get() == before + 1
    assert turning.get() == turned_before + 1  # the forward
    concatenated, broadcast = built_keys(eqns)
    assert not concatenated and not broadcast
    (call,) = [e for e in eqns if e.primitive.name == "custom_vjp_call"]
    q, kv, k_rope = call.invars[:3]
    assert q.aval.shape == (b, s, h * (n + r))
    assert kv.aval.shape == (b, s, h * (n + v))
    assert k_rope.aval.shape == (b, s, r)
    for operand in (q, kv):  # q_b and kv_b, nothing between
        (made,) = [e for e in eqns if operand in e.outvars]
        assert made.primitive.name == "dot_general"
    # the tables: one float32 [S, 2 r] operand, [cos | sin] over the lanes
    assert call.invars[5].aval.shape == (s, 2 * r)
    q_path = [e for e in eqns if any(
        tuple(o.aval.shape[2:]) in ((h, r // 2, 2), (h, r // 2), (h, r))
        for o in e.outvars
    )]
    assert not q_path, q_path  # no [.., 32, 32, 2] pairs, no cut of q
    grad_eqns = eqns_of(True, grad=True)
    assert counter.get() == before + 1 + 3
    assert turning.get() == turned_before + 1 + 2  # + forward and dQ
    # dQ's result reaches q_b's backward as it is: bfloat16, row-major
    assert not [
        e for e in grad_eqns
        if e.outvars and e.outvars[0].aval.shape == (b, s, h * (n + r))
        and e.outvars[0].aval.dtype == jnp.float32
    ]
    concatenated, broadcast = built_keys(eqns_of(False))
    assert len(concatenated) == 2 and len(broadcast) == 1
    assert counter.get() == before + 4
    assert turning.get() == turned_before + 3


def test_flash_path_with_the_kernels_rotary_equals_the_xla_path():
    """The model's output and every gradient, kernels (which rotate q
    themselves, interpreter) against ``use_flash=False`` (``rotary`` in
    XLA), at a length that is no multiple of the block."""
    cfg = _tiny(n_mtp=0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, cfg.d_model))
    w = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    params = LatentAttention(cfg).init(jax.random.PRNGKey(0), x)

    def run(use_flash):
        attn = LatentAttention(dataclasses.replace(cfg, use_flash=use_flash))

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
    _assert_trees_close(got, want, 2e-4)


def test_rotary_rotates_adjacent_pairs_and_keeps_relative_position():
    """``<rot(q, i), rot(k, j)>`` depends on ``i - j`` alone, position 0
    is the identity, and the plain reference's rotary agrees."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3, 8))
    out = rotary(x, theta=32e6)
    np.testing.assert_allclose(out[:, 0], x[:, 0], atol=1e-6)
    np.testing.assert_allclose(out, plain.rotary(x, 32e6), atol=1e-5)
    same = jnp.broadcast_to(x[:, :1], x.shape)  # one vector everywhere
    r = rotary(same, theta=100.0)[0, :, 0]
    gram = r @ r.T
    for off in range(1, 5):
        np.testing.assert_allclose(
            np.diag(gram, off), np.diag(gram, off)[0], rtol=1e-4
        )


# ------------------------------------------------------------ expert layer

def _layer_inputs(cfg, seed, tokens=64):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(keys[0], (1, tokens, cfg.d_model))
    layer = RoutedExperts(cfg)
    return layer, x, layer.init(keys[1], x)["params"]


def _louder(params, factor=10.0):
    """N(0, 0.02) weights give outputs too small to tell shares apart."""
    return jax.tree.map(lambda p: p * factor, params)


def test_shares_add_up_to_the_uncut_layer():
    """A 32-expert layer cut four ways, 8 experts a share: the four
    shares' routed parts, with the shared expert counted once, sum to what
    the plain reference gives for the whole layer (all 32 experts held)."""
    cfg = _tiny()
    whole = dataclasses.replace(cfg, n_experts_held=32)
    layer, x, params = _layer_inputs(whole, 3)
    params = _louder(params)
    want = plain.routed_experts(params, x, _sizes(whole))
    shared_only = plain.gated_mlp(
        x, *(params["shared"][n]["kernel"] for n in ("gate", "up", "down"))
    )
    total = shared_only
    for chip in range(4):
        share_cfg = dataclasses.replace(cfg, first_expert=8 * chip)
        share = {
            k: v[8 * chip:8 * chip + 8] if k.startswith("experts_") else v
            for k, v in params.items()
        }
        out = RoutedExperts(share_cfg).apply({"params": share}, x)
        # the chip's routed part: its output less the shared expert's
        total = total + (out - shared_only)
        np.testing.assert_allclose(
            out, plain.routed_experts(share, x, _sizes(share_cfg)),
            atol=2e-5,
        )
    assert float(jnp.abs(want - shared_only).max()) > 1e-2
    np.testing.assert_allclose(total, want, atol=5e-5)


def _route_and_run(x, router, bias, gate, up, down, *, first):
    chosen, weights = ep.topk_route(x, router, bias, top_k=4, scale=2.5)
    return ep.local_experts(
        x, chosen, weights, gate, up, down, first_expert=first,
        n_experts=router.shape[1],
    )


def _expert_operands(seed, tokens=64, d=16, f=24, experts=32, held=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(k[0], (tokens, d)),
        jax.random.normal(k[1], (d, experts)) * 0.3,
        jax.random.normal(k[2], (held, d, f)) * 0.2,
        jax.random.normal(k[3], (held, d, f)) * 0.2,
        jax.random.normal(k[4], (held, f, d)) * 0.2,
    )


def _plain_routed(x, router, gate, up, down, first):
    """The plain reference's routed part (it has no score bias)."""
    p = {"router": router, "experts_gate": gate, "experts_up": up,
         "experts_down": down}
    z = plain.Sizes(
        n_layers=0, n_dense_layers=0, n_heads=1, kv_lora_rank=0,
        qk_nope_dim=0, qk_rope_dim=0, v_dim=0, rope_theta=1.0,
        first_expert=first, top_k=4, routed_scale=2.5, n_mtp=0,
        mtp_weight=0.0,
    )
    return plain.routed_experts(p, x[None], z)[0]


@pytest.mark.parametrize("held_choices", [4, 2, 0])
def test_dropless_under_skew(held_choices):
    """A router forced onto the held experts (their score bias raised, so
    four, two or none of every token's choices are held here: 256, 128 or
    0 rows where 64 are expected): every term is there, output and every
    gradient equal the reference's."""
    x, router, gate, up, down = _expert_operands(4)
    first = 8
    forced = {4: slice(8, 16), 2: slice(8, 10), 0: slice(0, 8)}[held_choices]
    bias = jnp.zeros(32).at[forced].set(10.0)

    def system(x, router, gate, up, down):
        out = _route_and_run(x, router, bias, gate, up, down, first=first)
        return jnp.sum(out ** 2), out

    def reference(x, router, gate, up, down):
        scores = jax.nn.sigmoid(x @ router)
        _, chosen = jax.lax.top_k(scores + bias, 4)
        held = ((chosen >= first) & (chosen < first + 8)).sum(-1)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = picked / picked.sum(-1, keepdims=True) * 2.5
        out = jnp.zeros_like(x)
        for e in range(8):
            w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
            out = out + w[:, None] * plain.gated_mlp(
                x, gate[e], up[e], down[e]
            )
        return jnp.sum(out ** 2), (out, held)

    (got, out), grads = jax.jit(jax.value_and_grad(
        system, argnums=(0, 1, 2, 3, 4), has_aux=True
    ))(x, router, gate, up, down)
    (want, (ref_out, held)), ref_grads = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1, 2, 3, 4), has_aux=True
    ))(x, router, gate, up, down)
    assert bool((held >= held_choices).all())
    if not held_choices:  # a bias on other experts leaves some to chance
        assert int(held.sum()) < 64
    np.testing.assert_allclose(out, ref_out, atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(
            g, r, atol=1e-5 * float(jnp.abs(r).max()) + 1e-7
        )


def test_unforced_routing_matches_the_plain_reference():
    x, router, gate, up, down = _expert_operands(5)
    out = jax.jit(lambda *a: _route_and_run(
        a[0], a[1], jnp.zeros(32), *a[2:], first=16
    ))(x, router, gate, up, down)
    np.testing.assert_allclose(
        out, _plain_routed(x, router, gate, up, down, 16), atol=1e-5
    )


def _merged_experts(x, chosen, weights, gate, up, down, first):
    """The layer as it was first written, and its definition: gate and up
    as ONE dot each whose free dimension is ``(e, f)`` merged. (The TPU's
    compiler wants ``[held, d, f]`` as ``{1,2,0}`` for it and copied the
    stacks and their moments round and back every step, so the program
    batches the dots over ``e`` instead and writes their backward out:
    ``ep._gate_up``.)"""
    share = jnp.sum(
        jax.nn.one_hot(chosen - first, gate.shape[0]) * weights[..., None],
        axis=1,
    )
    hidden = jax.nn.silu(
        jnp.einsum("td,edf->tef", x, gate)
    ) * jnp.einsum("td,edf->tef", x, up)
    return jnp.einsum("tef,efd->td", hidden * share[..., None], down)


@pytest.mark.parametrize("held", [1, 3, 8])
@pytest.mark.parametrize("tokens", [50, 13])
def test_batched_dots_equal_the_merged_einsum_form(tokens, held):
    """Output and all five gradients (x, the router's through ``weights``,
    gate, up, down) of the layer against the merged-einsum form above, in
    float32: the same sums in another order."""
    first = 5
    x, router, gate, up, down = _expert_operands(
        8 + held, tokens=tokens, held=held
    )
    # half of every token's choices fall on the experts held here
    bias = jnp.zeros(32).at[first:first + min(held, 2)].set(10.0)

    def run(layer):
        def loss(x, router, gate, up, down):
            chosen, weights = ep.topk_route(
                x, router, bias, top_k=4, scale=2.5
            )
            out = layer(x, chosen, weights, gate, up, down)
            return jnp.sum(out ** 2), out

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(x, router, gate, up, down)

    (_, got), grads = run(lambda *a: ep.local_experts(
        *a, first_expert=first, n_experts=32
    ))
    (_, want), ref_grads = run(lambda *a: _merged_experts(*a, first))
    assert got.shape == (tokens, 16)
    for g, r in zip((got, *grads), (want, *ref_grads)):
        scale = float(jnp.abs(r).max())
        assert scale > 1e-3
        np.testing.assert_allclose(g, r, atol=1e-6 * scale, rtol=0)


def _matmul_flops(fn, *args):
    """FLOPs of every ``dot_general`` in ``fn``'s jaxpr, wherever it sits
    (the rematerialised ones too), and the primitives whose amount of work
    could follow the data."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in _sub_jaxprs_generic(eqn):
                yield from walk(sub)

    flops, names = [], set()
    for eqn in walk(jax.make_jaxpr(fn)(*args).jaxpr):
        names.add(eqn.primitive.name)
        if eqn.primitive.name != "dot_general":
            continue
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        out = eqn.outvars[0].aval.shape
        flops.append(2 * int(np.prod(out)) * int(
            np.prod([lhs[i] for i in lhs_contract])
        ))
    return sorted(flops), names


@pytest.mark.parametrize("tokens,held,experts", [(64, 8, 32), (96, 4, 32)])
def test_static_work_follows_the_shapes_not_the_seed(tokens, held, experts):
    """The expert matmuls, read off the jaxpr, run over ``held x T`` rows
    each, forward and backward, for two different seeds alike; and nothing
    in the layer could make the work follow the routing: no ``cond``, no
    ``while``, no sort, no gather, no scatter."""
    d, f = 16, 24

    def loss(x, router, gate, up, down):
        return jnp.sum(_route_and_run(
            x, router, jnp.zeros(experts), gate, up, down, first=0
        ))

    rows = held * tokens
    seen = []
    for seed in (0, 1):
        args = _expert_operands(seed, tokens=tokens, experts=experts,
                                held=held)
        flops, names = _matmul_flops(
            jax.grad(loss, argnums=(0, 2, 3, 4)), *args
        )
        # gate / up / down forward, the first two again in the backward's
        # recomputation, and two matmuls each in the backward (gate's and
        # up's dW are one matmul of twice the size): 11 of that size
        unit = 2 * rows * d * f
        assert sum(n // unit for n in flops if not n % unit) >= 11, flops
        assert not names & {
            "cond", "while", "sort", "gather", "scatter", "scatter-add",
            "scatter_add", "dynamic_slice",
        }, names
        seen.append(flops)
    assert seen[0] == seen[1]


def test_build_time_counters_book_buffer_and_expectation():
    reg = registry.always()
    buffered, expected = (
        reg.counter("moe.rows_buffered"), reg.counter("moe.rows_expected")
    )
    args = _expert_operands(6)
    before = buffered.get(), expected.get()
    jax.eval_shape(lambda *a: _route_and_run(
        a[0], a[1], jnp.zeros(32), *a[2:], first=0
    ), *args)
    assert buffered.get() - before[0] == 8 * 64
    assert expected.get() - before[1] == 64


def test_router_scores_in_float32_and_weights_sum_to_the_scale():
    x, router, *_ = _expert_operands(7)
    chosen, weights = ep.topk_route(
        x.astype(jnp.bfloat16), router, jnp.zeros(32), top_k=4, scale=2.5
    )
    assert chosen.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    scores = jax.nn.sigmoid(
        x.astype(jnp.bfloat16).astype(jnp.float32) @ router
    )
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(jax.lax.top_k(scores, 4)[1], -1)
    )
    # the bias steers the choice and is no part of the weight
    bias = jnp.zeros(32).at[3].set(5.0)
    chosen_b, weights_b = ep.topk_route(x, router, bias, top_k=4, scale=1.0)
    assert bool((chosen_b == 3).any(-1).all())
    picked = jnp.take_along_axis(jax.nn.sigmoid(x @ router), chosen_b, -1)
    np.testing.assert_allclose(
        weights_b, picked / picked.sum(-1, keepdims=True), rtol=1e-5
    )
