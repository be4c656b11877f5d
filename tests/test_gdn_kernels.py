"""The scalar-gate form of the delta-rule kernel family
(``ops/kda_kernels.py``: Gated DeltaNet, ``g`` one log-decay a head, kernels
``hvd_gdn_fwd`` / ``hvd_gdn_bwd``) through the Pallas interpreter, at head
widths with ``d_k != d_v`` in the published 1 : 2 ratio and no power of two
(12 / 24), against the recurrence written out here a position at a time in
float32: the output and every gradient (q, k, v, g, beta and, with
``conv=``, the three taps'), with and without the door (``conv=``) and the
exit (``out_norm=``); against the channel-wise form given ``g`` broadcast
over a head's key channels; the strongest decay the configuration's initial
values give, with ``beta`` up to 2; bfloat16 operands; a row of zeros that
``eps`` alone keeps finite; that the chunk size does not change the result;
the counters and names; and that a channel-wise call still builds the
``hvd_kda_*`` kernels it built and books nothing of this form's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.linear_moe import conv_silu
from horovod_tpu.obs import registry
from horovod_tpu.ops.kda_kernels import KdaConv, kda_attention, kda_recurrence

_HI = jax.lax.Precision.HIGHEST
DK, DV = 12, 24
EPS = 1e-6  # the configuration's


def recurrence(q, k, v, g, beta, *, n_heads):
    """``S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T;
    o_t = S_t q_t`` with q, k L2-normalised a head (q times d_k^-1/2) and
    ``S [d_v, d_k]``: a plain ``lax.scan``, nothing kept of the module's."""
    b, s, _ = q.shape
    heads = lambda x: x.astype(jnp.float32).reshape(b, s, n_heads, -1)  # noqa: E731
    q, k, v = heads(q), heads(k), heads(v)
    unit = lambda x: x / jnp.sqrt(  # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6
    )
    q, k = unit(q) / np.sqrt(q.shape[-1]), unit(k)
    eye = jnp.eye(q.shape[-1])

    def step(state, x):  # [b, h, dv, dk]
        q_t, k_t, v_t, g_t, beta_t = x
        forget = eye - beta_t[..., None, None] * jnp.einsum(
            "bhi,bhj->bhij", k_t, k_t, precision=_HI
        )
        state = jnp.exp(g_t)[..., None, None] * jnp.einsum(
            "bhvi,bhij->bhvj", state, forget, precision=_HI
        ) + beta_t[..., None, None] * jnp.einsum(
            "bhv,bhk->bhvk", v_t, k_t, precision=_HI
        )
        return state, jnp.einsum("bhvk,bhk->bhv", state, q_t, precision=_HI)

    state = jnp.zeros((b, n_heads, v.shape[-1], q.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def operands(b, s, h, *, seed=0, decay=0.3, dtype=jnp.float32, dk=DK, dv=DV):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    draw = lambda k, w: jax.random.normal(k, (b, s, w), jnp.float32)  # noqa: E731
    g = -jax.random.uniform(keys[3], (b, s, h), minval=0.0, maxval=decay)
    # beta in (0, 2): the factor 2 of ``allow_neg_eigval``
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))
    q, k = (draw(keys[i], h * dk).astype(dtype) for i in range(2))
    v = draw(keys[2], h * dv).astype(dtype)
    return (q, k, v, g, beta), draw(keys[5], h * dv)


def taps_for(h, *, n=4, seed=7, dk=DK, dv=DV):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return KdaConv(*(
        jax.random.uniform(k, (n, h * d), jnp.float32, -0.5, 0.5)
        for k, d in zip(keys, (dk, dk, dv))
    ))


def head_norm(o, h, eps=EPS):
    b, s, _ = o.shape
    heads = o.astype(jnp.float32).reshape(b, s, h, -1)
    return (heads * jax.lax.rsqrt(
        jnp.mean(heads * heads, -1, keepdims=True) + eps
    )).reshape(b, s, -1)


def value_and_grads(fn, weights, argv, taps):
    def scalar(argv, taps):
        return jnp.sum(fn(argv, taps).astype(jnp.float32) * weights)

    argnums = (0, 1) if taps is not None else (0,)
    return jax.jit(jax.value_and_grad(scalar, argnums=argnums))(argv, taps)


def both(argv, weights, h, *, conv=False, norm=False, ref=recurrence,
         **statics):
    """``(kernels, reference)`` values and gradients, the reference with
    the door and the exit written out around it."""
    taps = taps_for(h, dk=argv[0].shape[-1] // h, dv=argv[2].shape[-1] // h
                    ) if conv else None

    def kernels(argv, taps):
        return kda_attention(
            *argv, n_heads=h, use_kernel=True, conv=taps,
            out_norm=EPS if norm else None, **statics,
        )

    def written_out(argv, taps):
        q, k, v, g, beta = argv
        if taps is not None:
            q, k, v = (conv_silu(x, w) for x, w in zip((q, k, v), taps))
        o = ref(q, k, v, g, beta, n_heads=h)
        return head_norm(o, h) if norm else o

    return (value_and_grads(kernels, weights, argv, taps),
            value_and_grads(written_out, weights, argv, taps))


def assert_close(got, want, tol):
    flat_got, flat_want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for a, b in zip(flat_got, flat_want):
        assert a.shape == b.shape
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        assert float(jnp.max(jnp.abs(a - b))) / scale < tol, (
            a.shape, float(jnp.max(jnp.abs(a - b))) / scale
        )


@pytest.mark.parametrize("conv", [False, True], ids=["plain", "conv"])
@pytest.mark.parametrize("shape,norm", [
    ((1, 64, 3), False), ((2, 150, 2), False), ((2, 150, 2), True),
    ((1, 40, 1), True),
], ids=["1x64x3", "2x150x2", "2x150x2-norm", "1x40x1-norm"])
def test_out_and_every_gradient_equal_the_recurrence(shape, norm, conv):
    b, s, h = shape
    argv, weights = operands(b, s, h)
    got, want = both(argv, weights, h, conv=conv, norm=norm, chunk=16)
    assert_close(got, want, 2e-5)


@pytest.mark.parametrize("conv,norm", [(False, False), (True, True)],
                         ids=["plain", "conv-norm"])
def test_the_scalar_form_is_the_channel_wise_form_on_a_broadcast_gate(
        conv, norm):
    """The same operands through ``hvd_kda_*`` with ``g`` repeated over a
    head's key channels: one family, two forms, one result."""
    b, s, h = 1, 80, 2
    argv, weights = operands(b, s, h)
    taps = taps_for(h) if conv else None

    def form(channel_wise):
        def fn(argv, taps):
            q, k, v, g, beta = argv
            if channel_wise:
                g = jnp.repeat(g, DK, axis=-1)
            return kda_attention(
                q, k, v, g, beta, n_heads=h, use_kernel=True, conv=taps,
                out_norm=EPS if norm else None, chunk=16,
            )
        return value_and_grads(fn, weights, argv, taps)

    assert_close(form(False), form(True), 2e-5)


@pytest.mark.parametrize("norm", [False, True], ids=["plain", "norm"])
def test_the_strongest_assumed_decay_stays_finite_and_exact(norm):
    """``A`` up to 16 and ``dt`` up to 0.1 give ``g`` down to -1.6 a step:
    over a 64-row chunk the cumulated log-decay reaches -100, whose
    reciprocal overflows float32; every exponent formed is <= 0."""
    argv, weights = operands(1, 128, 2, decay=1.6, seed=3)
    got, want = both(argv, weights, 2, norm=norm, conv=True)
    for leaf in jax.tree.leaves(got):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    assert_close(got, want, 2e-5)


@pytest.mark.parametrize("conv,norm", [(False, False), (True, True)],
                         ids=["plain", "conv-norm"])
def test_bfloat16_operands_stay_within_their_rounding(conv, norm):
    argv, weights = operands(1, 128, 2, dtype=jnp.bfloat16, seed=5)
    got, want = both(argv, weights, 2, conv=conv, norm=norm)
    assert got[1][0][0].dtype == jnp.bfloat16  # dq in the operands' dtype
    assert got[1][0][3].dtype == got[1][0][4].dtype == jnp.float32
    assert_close(got, want, 6e-2)


def test_a_row_of_zeros_is_normalised_by_eps_alone():
    """``v = 0`` everywhere: every output row is zero, its mean square is
    zero, and ``eps`` alone keeps ``1 / rms`` and the gradients finite."""
    (q, k, v, g, beta), weights = operands(1, 64, 2)
    argv = (q, k, jnp.zeros_like(v), g, beta)
    got, want = both(argv, weights, 2, norm=True)
    for leaf in jax.tree.leaves(got):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    assert_close(got, want, 2e-5)


def test_no_decay_and_full_writes_are_the_plain_delta_rule():
    """``g = 0``, ``beta = 1``: the state stores ``v`` at ``k`` and a read
    with the same key returns it (times the ``d_k^-1/2`` q carries)."""
    h = 1
    (q, k, v, g, beta), _ = operands(1, 32, h)
    k = jnp.zeros_like(k).at[..., 0].set(1.0)  # every key the same unit
    out = kda_attention(
        k, k, v, jnp.zeros_like(g), jnp.ones_like(beta), n_heads=h,
        use_kernel=True, chunk=16,
    )
    np.testing.assert_allclose(out * np.sqrt(DK), v, atol=1e-5)


def test_the_chunk_size_does_not_change_the_result():
    argv, weights = operands(1, 128, 2, seed=2)
    small, _ = both(argv, weights, 2, chunk=16, conv=True, norm=True)
    large, _ = both(argv, weights, 2, chunk=64, conv=True, norm=True)
    assert_close(small, large, 2e-5)


def test_the_recurrence_path_takes_a_gate_a_head():
    """Off the TPU the default is the recurrence, which broadcasts a
    ``[B, S, H]`` gate over a head's state as it stands."""
    argv, weights = operands(2, 40, 3)
    got = kda_attention(*argv, n_heads=3)
    want = recurrence(*argv, n_heads=3)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(
        kda_recurrence(*argv, n_heads=3), want, atol=2e-6
    )


def _counts(prefix):
    reg = registry.always()
    names = ("calls", "calls.conv", "calls.out_norm", "calls.lanes_padded",
             "chunks", "state_bytes_saved")
    return {n: reg.counter(f"{prefix}.{n}").get() for n in names}


@pytest.mark.parametrize("conv,norm", [(False, False), (True, True)],
                         ids=["plain", "conv-norm"])
def test_counters_count_what_they_say(conv, norm):
    b, s, h = 2, 150, 2
    argv, weights = operands(b, s, h)
    before, kda_before = _counts("gdn"), _counts("kda")
    both(argv, weights, h, conv=conv, norm=norm, chunk=16)
    after = _counts("gdn")
    grew = {n: after[n] - before[n] for n in after}
    s_pad = 256  # blocks of 128 rows
    assert grew == {
        "calls": 2, "calls.conv": 2 * conv, "calls.out_norm": 2 * norm,
        "calls.lanes_padded": 2,  # 12 and 24 are no whole lane tiles
        "chunks": b * h * s_pad // 16,
        "state_bytes_saved": b * h * (s_pad // 16) * DV * DK * 4,
    }
    assert _counts("kda") == kda_before  # the other form books its own


def _kernel_calls(fn, *args):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((
                    eqn.params["name"], str(eqn.source_info.name_stack)
                ))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("scalar", [True, False], ids=["scalar", "channel"])
def test_the_gates_shape_chooses_the_kernels_and_they_carry_no_scope(scalar):
    """``g [B, S, H]`` builds ``hvd_gdn_*``; ``g [B, S, H d_k]`` the
    ``hvd_kda_*`` kernels it always built: no flag chooses."""
    h = 2
    (q, k, v, g, beta), weights = operands(1, 64, h, dk=16, dv=16)
    if not scalar:
        g = jnp.repeat(g, 16, axis=-1)

    def loss(q, k, v, g, beta):
        with jax.named_scope("outer"):
            return jnp.sum(kda_attention(
                q, k, v, g, beta, n_heads=h, use_kernel=True, chunk=16,
            ) * weights)

    calls = _kernel_calls(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                          q, k, v, g, beta)
    stem = "hvd_gdn_" if scalar else "hvd_kda_"
    assert [name for name, _ in calls] == [stem + "fwd", stem + "bwd"]
    for _, stack in calls:
        assert "attn_layout" not in stack and "kda_conv" not in stack


def test_widths_off_the_lanes_are_refused_in_the_channel_wise_form_only():
    """Compiled for the TPU (``interpret=False``) the channel-wise plan
    wants whole 128-lane tiles a head; the scalar-gate plan takes 96 /
    192."""
    from horovod_tpu.ops import kda_kernels

    q = jax.ShapeDtypeStruct((1, 256, 2 * 96), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 256, 2 * 192), jnp.bfloat16)
    beta = jax.ShapeDtypeStruct((1, 256, 2), jnp.float32)
    plan = lambda scalar: kda_kernels._plan(  # noqa: E731
        q, v, beta, n_heads=2, chunk=None, sub=None, interpret=False,
        scalar=scalar,
    )
    with pytest.raises(ValueError, match="128-lane"):
        plan(False)
    p = plan(True)
    assert (p.dk, p.dv, p.block, p.scalar) == (96, 192, 128, True)
