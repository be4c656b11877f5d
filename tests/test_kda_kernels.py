"""The Kimi Delta Attention kernel family (``ops/kda_kernels.py``) through
the Pallas interpreter against the recurrence written out here a position
at a time in float32: the output and every gradient (q, k, v, g, beta),
over chunk counts, head widths, batches and heads; the strongest decay the
configuration's initial values give; the two reductions the delta rule
has; that neither the chunk nor the sub-block size changes the result; the
counters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.obs import registry
from horovod_tpu.ops import kda_kernels
from horovod_tpu.ops.kda_kernels import kda_attention, kda_recurrence

_HI = jax.lax.Precision.HIGHEST


def recurrence(q, k, v, g, beta, *, n_heads):
    """``S' = Diag(exp g_t) S; S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S_t^T q_t`` with q, k L2-normalised a head (q times d^-1/2): a
    plain ``lax.scan``, nothing kept of the module's."""
    b, s, _ = q.shape
    heads = lambda x: x.astype(jnp.float32).reshape(b, s, n_heads, -1)  # noqa: E731
    q, k, v, g = heads(q), heads(k), heads(v), heads(g)
    unit = lambda x: x / jnp.sqrt(  # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6
    )
    q, k = unit(q) / np.sqrt(q.shape[-1]), unit(k)

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HI)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read),
            precision=_HI,
        )
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HI)

    state = jnp.zeros((b, n_heads, q.shape[-1], v.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def operands(b, s, h, d, *, seed=0, decay=0.3, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    draw = lambda k, w: jax.random.normal(k, (b, s, w), jnp.float32)  # noqa: E731
    g = -jax.random.uniform(keys[3], (b, s, h * d), minval=0.0, maxval=decay)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, s, h)))
    q, k, v = (draw(keys[i], h * d).astype(dtype) for i in range(3))
    return (q, k, v, g, beta), draw(keys[5], h * d)


def both(argv, weights, h, **statics):
    """``(out, gradients)`` of the kernels and of the recurrence."""
    def loss(fn):
        def of(*a):
            out = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * weights), out
        return jax.value_and_grad(of, argnums=(0, 1, 2, 3, 4), has_aux=True)

    kernels = lambda *a: kda_attention(  # noqa: E731
        *a, n_heads=h, use_kernel=True, **statics
    )
    plain = lambda *a: recurrence(*a, n_heads=h)  # noqa: E731
    (_, out), grads = loss(kernels)(*argv)
    (_, want), want_grads = loss(plain)(*argv)
    return (out, *grads), (want, *want_grads)


def assert_close(got, want, tol):
    for name, a, e in zip(("out", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        assert np.isfinite(a).all(), name
        scale = max(float(np.abs(e).max()), 1e-6)
        assert float(np.abs(a - e).max()) <= tol * scale, (
            name, float(np.abs(a - e).max()), scale
        )


@pytest.mark.parametrize("b,s,h,d,chunk,sub", [
    (1, 16, 1, 16, 16, 8),    # one chunk
    (2, 48, 2, 16, 16, 8),    # three chunks, batch and heads > 1
    (1, 40, 2, 16, 16, 16),   # no multiple of the chunk: padded
    (1, 40, 1, 128, 32, 8),   # heads of 128, four sub-blocks a chunk
    (1, 128, 1, 128, 64, 8),  # the plan's own chunk and sub-block
], ids=["1-chunk", "3-chunks", "ragged", "d128", "plan"])
def test_out_and_every_gradient_equal_the_recurrence(b, s, h, d, chunk, sub):
    argv, weights = operands(b, s, h, d)
    got, want = both(argv, weights, h, chunk=chunk, sub=sub)
    assert got[0].shape == (b, s, h * d)
    assert_close(got, want, 2e-5)


def test_the_strongest_assumed_decay_stays_finite_and_exact():
    """``A`` 16 and ``dt`` 0.1 over a whole chunk of 64: ``g`` = -1.6 a
    step, 102 over the chunk, so ``exp(-G)`` is past float32; every
    exponent the kernels form is <= 0, so nothing overflows and the small
    numbers that are left equal the recurrence's."""
    b, s, h, d = 1, 128, 1, 128
    argv, weights = operands(b, s, h, d, seed=3)
    q, k, v, _, beta = argv
    g = jnp.full((b, s, h * d), -16.0 * 0.1, jnp.float32)
    assert float(jnp.exp(64 * 1.6)) == np.inf  # what the cheap form meets
    got, want = both((q, k, v, g, beta), weights, h, chunk=64, sub=8)
    assert float(jnp.abs(want[0]).max()) > 1e-3  # not a comparison of zeros
    assert_close(got, want, 2e-5)


def test_bfloat16_operands_stay_within_their_rounding():
    argv, weights = operands(1, 64, 2, 16, dtype=jnp.bfloat16)
    got, want = both(argv, weights, 2, chunk=16, sub=8)
    assert got[0].dtype == jnp.bfloat16 and got[4].dtype == jnp.float32
    assert_close(got, want, 4e-2)


def test_no_decay_and_full_writes_are_the_plain_delta_rule():
    """``g = 0, beta = 1``: ``S_t = (I - k_t k_t^T) S_{t-1} + k_t v_t^T``,
    so reading the state with the key just written gives the value back."""
    b, s, h, d = 1, 32, 1, 16
    (q, k, v, _, _), _ = operands(b, s, h, d, seed=1)
    out = kda_attention(
        k, k, v, jnp.zeros((b, s, h * d)), jnp.ones((b, s, h)), n_heads=h,
        use_kernel=True, chunk=16, sub=8,
    )
    # q = k: o_t = S_t^T k_t d^-1/2 = v_t d^-1/2 (|k_t| = 1 after the norm)
    np.testing.assert_allclose(out, v / np.sqrt(d), rtol=0, atol=2e-5)


def test_no_writes_leave_the_state_decayed_only():
    """``beta = 0`` from position 16 on: the state is what position 15 left,
    times the decays since, so ``o_t = (S_15 * exp(G_t - G_15))^T q_t``."""
    b, s, h, d = 1, 48, 1, 16
    (q, k, v, g, beta), _ = operands(b, s, h, d, seed=2)
    beta = beta.at[:, 16:].set(0.0)
    out = kda_attention(q, k, v, g, beta, n_heads=h, use_kernel=True,
                        chunk=16, sub=8)
    # the state after position 15, from the recurrence on the first 16 rows
    # with one-hot queries
    eye = jnp.eye(d)[None].repeat(b, 0)  # [b, d, d]: query c reads row c
    first = lambda x: x[:, :16]  # noqa: E731
    rows = []
    for c in range(d):
        probe = first(q).at[:, 15].set(eye[:, c])
        rows.append(recurrence(
            probe, first(k), first(v), first(g), first(beta), n_heads=h
        )[:, 15] * np.sqrt(d))
    state = jnp.stack(rows, axis=1)  # [b, d_k, d_v]
    decay = jnp.exp(jnp.cumsum(g[:, 16:], axis=1))  # [b, 32, d_k]
    qn = q[:, 16:] / jnp.sqrt(
        jnp.sum(q[:, 16:] ** 2, -1, keepdims=True) + 1e-6
    ) / np.sqrt(d)
    want = jnp.einsum("btk,bkv->btv", qn * decay, state, precision=_HI)
    np.testing.assert_allclose(out[:, 16:], want, rtol=0, atol=2e-5)


def test_chunk_and_sub_block_sizes_do_not_change_the_result():
    """The state crosses chunk borders whole: four chunks of 16, two of 32
    and one of 64 give the same numbers, whatever the sub-block (64 / 16
    is the first parametrised test's ``plan`` case's neighbour)."""
    argv, weights = operands(1, 64, 2, 16, seed=4)
    runs = [both(argv, weights, 2, chunk=c, sub=s)[0]
            for c, s in ((16, 8), (32, 16), (64, 8))]
    for other in runs[1:]:
        assert_close(other, runs[0], 2e-5)


def test_recurrence_path_is_the_default_off_the_tpu_and_agrees():
    argv, weights = operands(2, 40, 2, 16, seed=5)
    default = kda_attention(*argv, n_heads=2)
    np.testing.assert_allclose(
        default, recurrence(*argv, n_heads=2), rtol=0, atol=2e-6
    )
    np.testing.assert_allclose(
        kda_recurrence(*argv, n_heads=2, group=16), default, rtol=0,
        atol=2e-6,
    )


def test_counters_count_what_they_say():
    reg = registry.always()
    names = ("kda.calls", "kda.chunks", "kda.state_bytes_saved")
    before = [reg.counter(n).get() for n in names]
    b, s, h, d = 2, 40, 2, 16
    argv, weights = operands(b, s, h, d, seed=6)
    jax.eval_shape(jax.grad(lambda *a: jnp.sum(kda_attention(
        *a, n_heads=h, use_kernel=True, chunk=16, sub=8
    ) * weights), argnums=(0, 1, 2, 3, 4)), *argv)  # built, not run
    calls, chunks, saved = (
        reg.counter(n).get() - was for n, was in zip(names, before)
    )
    assert calls == 2  # the forward and the backward
    assert chunks == b * h * 3  # 40 rows padded to 48: three chunks of 16
    assert saved == chunks * d * d * 4  # one float32 [d_v, d_k] state each
    plan = kda_kernels._plan(
        argv[0].astype(jnp.bfloat16), argv[2], argv[4], n_heads=h,
        chunk=None, sub=None, interpret=True,
    )
    # the cell's call: 64-row chunks, bfloat16 states
    assert (plan.chunk, plan.sub) == (64, 8)
    assert plan.state_bytes == b * h * d * d * 2


def test_kernels_are_named_for_the_trace_and_carry_no_scope():
    argv, weights = operands(1, 32, 1, 16)
    traced = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda_attention(
        *a, n_heads=1, use_kernel=True, chunk=16, sub=8
    ) * weights)))(*argv)
    from horovod_tpu.analysis.jaxpr_walk import _sub_jaxprs_generic

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in _sub_jaxprs_generic(eqn):
                yield from walk(sub)

    calls = [e for e in walk(traced.jaxpr) if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == [
        "hvd_kda_bwd", "hvd_kda_fwd"
    ]
    for e in calls:
        assert "attn_layout" not in str(e.source_info.name_stack)
