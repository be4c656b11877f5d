"""The Kimi Delta Attention kernel family (``ops/kda_kernels.py``) through
the Pallas interpreter against the recurrence written out here a position
at a time in float32: the output and every gradient (q, k, v, g, beta),
over chunk counts, head widths, batches and heads; the strongest decay the
configuration's initial values give; the two reductions the delta rule
has; that neither the chunk nor the sub-block size changes the result; the
counters. With ``conv=`` (the kernels convolve and gate q, k, v at their
door) the same cases again, the three taps' gradients too, against
``models.linear_moe.conv_silu`` in front of the recurrence AND in front of
the kernels without taps; a block's border is causal; a call without taps
builds the kernels it built. With ``out_norm=`` (the kernels normalise a
head's output at their exit) cases of the same tests again: the output,
``1 / rms`` and every gradient against the model's head-wise float32 RMS
norm written out here, behind the recurrence AND behind the kernels without
the argument; a row of zeros, which ``eps`` alone keeps finite; a call
without the argument builds the kernels it built.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.linear_moe import conv_silu
from horovod_tpu.obs import registry
from horovod_tpu.ops import kda_kernels
from horovod_tpu.ops.kda_kernels import KdaConv, kda_attention, kda_recurrence

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


def taps_for(h, d, *, n=4, seed=7):
    """Taps as the model draws them, U(-0.5, 0.5)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return KdaConv(*(
        jax.random.uniform(k, (n, h * d), jnp.float32, -0.5, 0.5)
        for k in keys
    ))


EPS = 1e-5  # the configuration's


def head_norm(o, h, eps=EPS):
    """``o / sqrt(mean_d(o^2) + eps)`` over each of ``h`` heads' channels,
    in float32: ``models.linear_moe``'s gated norm without its scale and
    gate."""
    b, s, width = o.shape
    heads = o.astype(jnp.float32).reshape(b, s, h, -1)
    return (heads / jnp.sqrt(
        jnp.mean(heads * heads, axis=-1, keepdims=True) + eps
    )).reshape(b, s, width)


def value_and_grads(fn, weights, argv):
    """``(out, gradients...)`` of ``fn`` in every operand of ``argv``."""
    def of(*a):
        out = fn(*a)
        return jnp.sum(out.astype(jnp.float32) * weights), out
    (_, out), grads = jax.value_and_grad(
        of, argnums=tuple(range(len(argv))), has_aux=True
    )(*argv)
    return (out, *grads)


def both(argv, weights, h, conv=None, norm=False, **statics):
    """``(out, gradients)`` of the kernels and of the recurrence; with
    ``conv`` the kernels are given the taps (three more gradients) and
    there are two references: ``conv_silu`` in front of the recurrence
    and in front of the kernels without taps. With ``norm`` the kernels
    are given ``out_norm=EPS`` and the references are :func:`head_norm`
    behind the recurrence and behind the SAME kernels without it."""
    kernels = lambda *a, **kw: kda_attention(  # noqa: E731
        *a, n_heads=h, use_kernel=True, **statics, **kw
    )
    plain = lambda *a: recurrence(*a, n_heads=h)  # noqa: E731
    run = lambda fn, argv: value_and_grads(fn, weights, argv)  # noqa: E731

    def convolved(fn):
        return lambda q, k, v, g, beta, *taps: fn(
            *(conv_silu(x, w) for x, w in zip((q, k, v), taps)), g, beta
        )

    if conv is None:
        mine, others = kernels, [plain]
    else:
        argv = (*argv, *conv)
        mine = lambda *a, **kw: kernels(  # noqa: E731
            *a[:5], conv=KdaConv(*a[5:]), **kw
        )
        others = [convolved(plain), convolved(kernels)]
    if not norm:
        return (run(mine, argv), *(run(fn, argv) for fn in others))
    behind = lambda fn: lambda *a: head_norm(fn(*a), h)  # noqa: E731
    return (run(functools.partial(mine, out_norm=EPS), argv),
            run(behind(others[0]), argv), run(behind(mine), argv))


NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta", "dtaps_q", "dtaps_k",
         "dtaps_v")


def assert_close(got, want, tol):
    for name, a, e in zip(NAMES, got, want):
        a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
        assert np.isfinite(a).all(), name
        scale = max(float(np.abs(e).max()), 1e-6)
        assert float(np.abs(a - e).max()) <= tol * scale, (
            name, float(np.abs(a - e).max()), scale
        )


CONV = pytest.mark.parametrize("conv", [False, True], ids=["plain", "conv"])
NORM = pytest.mark.parametrize("norm", [False, True], ids=["", "out-norm"])

SHAPES = {
    "1-chunk": (1, 16, 1, 16, 16, 8),
    "3-chunks": (2, 48, 2, 16, 16, 8),  # batch and heads > 1
    "ragged": (1, 40, 2, 16, 16, 16),  # no multiple of the chunk: padded
    "d128": (1, 40, 1, 128, 32, 8),  # heads of 128, four sub-blocks a chunk
    "plan": (1, 128, 1, 128, 64, 8),  # the plan's own chunk and sub-block
    "3-rows": (1, 3, 1, 16, 16, 8),  # shorter than a convolution's taps
    "2-blocks": (2, 200, 2, 16, 16, 8),  # two grid steps, the second ragged
}
# the exit norm: where 1 / rms leaves as one row vector (one chunk), as a
# piece of one (three chunks), past the sequence's end (ragged, two blocks)
# and as the plan's two chunks a 128-lane tile
NORMED = ("1-chunk", "3-chunks", "ragged", "plan", "2-blocks")


@CONV
@pytest.mark.parametrize("shape,norm", [
    pytest.param(name, norm, id=name + ("-out-norm" if norm else ""))
    for norm in (False, True) for name in (NORMED if norm else SHAPES)
])
def test_out_and_every_gradient_equal_the_recurrence(shape, norm, conv):
    b, s, h, d, chunk, sub = SHAPES[shape]
    argv, weights = operands(b, s, h, d)
    got, *wants = both(argv, weights, h, conv=taps_for(h, d) if conv else None,
                       norm=norm, chunk=chunk, sub=sub)
    assert got[0].shape == (b, s, h * d)
    assert len(got) == (9 if conv else 6)
    assert len(wants) == (2 if norm else 1 + conv)
    for want in wants:
        assert_close(got, want, 2e-5)


@NORM
def test_the_strongest_assumed_decay_stays_finite_and_exact(norm):
    """``A`` 16 and ``dt`` 0.1 over a whole chunk of 64: ``g`` = -1.6 a
    step, 102 over the chunk, so ``exp(-G)`` is past float32; every
    exponent the kernels form is <= 0, so nothing overflows and the small
    numbers that are left equal the recurrence's."""
    b, s, h, d = 1, 128, 1, 128
    argv, weights = operands(b, s, h, d, seed=3)
    q, k, v, _, beta = argv
    g = jnp.full((b, s, h * d), -16.0 * 0.1, jnp.float32)
    assert float(jnp.exp(64 * 1.6)) == np.inf  # what the cheap form meets
    got, *wants = both((q, k, v, g, beta), weights, h, norm=norm, chunk=64,
                       sub=8)
    assert float(jnp.abs(wants[0][0]).max()) > 1e-3  # no comparison of zeros
    for want in wants:
        assert_close(got, want, 2e-5)


@NORM
@CONV
def test_bfloat16_operands_stay_within_their_rounding(conv, norm):
    """With taps the convolved values stay float32 into the norm (one
    rounding fewer than ``conv_silu``'s bfloat16 output, never one more);
    with the exit norm the float32 output is normalised and rounded once
    (where the norm behind the kernels reads the rounded one)."""
    argv, weights = operands(1, 64, 2, 16, dtype=jnp.bfloat16)
    got, *wants = both(argv, weights, 2, conv=taps_for(2, 16) if conv else
                       None, norm=norm, chunk=16, sub=8)
    assert got[0].dtype == jnp.bfloat16 and got[4].dtype == jnp.float32
    assert all(x.dtype == jnp.float32 for x in got[6:])
    for want in wants:
        assert_close(got, want, 4e-2)


def _rstd(argv, h, conv=None, **statics):
    """``1 / rms`` as the forward leaves it for the backward, ``[B, S,
    H]``, beside the normalised output."""
    plan = kda_kernels._plan(argv[0], argv[2], argv[4], n_heads=h,
                             interpret=True, conv=conv, out_norm=EPS,
                             **statics)
    out, (*_, normed) = kda_kernels._kda_fwd(*argv, conv, plan)
    assert normed[0] is out  # the one copy kept
    return out, normed[1]


@CONV
def test_a_row_of_zeros_is_normalised_by_eps_alone(conv):
    """Rows 8..11 of q are zeros (with taps: row 11 of the convolved q,
    and ``SiLU(0) = 0``), so row 11 of the output is zeros for every head
    and ``1 / rms`` there is ``eps^-1/2``, finite; the row's gradients are
    the reference's, ``eps^-1/2`` times the cotangent behind the norm.
    Everywhere else ``1 / rms`` is the recurrence's, in the layout the
    backward reads (two grid steps, the second ragged)."""
    b, s, h, d, chunk, sub = SHAPES["2-blocks"]
    (q, k, v, g, beta), weights = operands(b, s, h, d, seed=9)
    argv = (q.at[:, 8:12].set(0.0), k, v, g, beta)
    taps = taps_for(h, d) if conv else None
    out, rstd = _rstd(argv, h, conv=taps, chunk=chunk, sub=sub)
    assert rstd.shape == (b, s, h) and rstd.dtype == jnp.float32
    assert (np.asarray(out)[:, 11] == 0).all()
    np.testing.assert_allclose(rstd[:, 11], EPS ** -0.5, rtol=1e-6)
    plain = recurrence(*(
        conv_silu(x, w) for x, w in zip(argv[:3], taps)
    ), g, beta, n_heads=h) if conv else recurrence(*argv, n_heads=h)
    want = 1.0 / np.sqrt(np.mean(
        np.asarray(plain).reshape(b, s, h, d) ** 2, axis=-1
    ) + EPS)
    np.testing.assert_allclose(rstd, want, rtol=2e-5)
    got, *wants = both(argv, weights, h, conv=taps, norm=True, chunk=chunk,
                       sub=sub)
    assert float(np.abs(np.asarray(got[1])[:, 11]).max()) > 1.0  # dq there
    for want in wants:
        assert_close(got, want, 2e-5)


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


@NORM
@CONV
def test_counters_count_what_they_say(conv, norm):
    reg = registry.always()
    names = ("kda.calls", "kda.chunks", "kda.state_bytes_saved",
             "kda.calls.conv", "kda.calls.out_norm")
    before = [reg.counter(n).get() for n in names]
    b, s, h, d = 2, 40, 2, 16
    argv, weights = operands(b, s, h, d, seed=6)
    taps = taps_for(h, d) if conv else None
    jax.eval_shape(jax.grad(lambda *a: jnp.sum(kda_attention(
        *a, n_heads=h, conv=taps, out_norm=EPS if norm else None,
        use_kernel=True, chunk=16, sub=8,
    ) * weights), argnums=(0, 1, 2, 3, 4)), *argv)  # built, not run
    calls, chunks, saved, convolving, normalising = (
        reg.counter(n).get() - was for n, was in zip(names, before)
    )
    assert calls == 2  # the forward and the backward
    assert convolving == (2 if conv else 0)  # of them, those that convolve
    assert normalising == (2 if norm else 0)  # ... that normalise their exit
    assert chunks == b * h * 3  # 40 rows padded to 48: three chunks of 16
    assert saved == chunks * d * d * 4  # one float32 [d_v, d_k] state each
    plan = kda_kernels._plan(
        argv[0].astype(jnp.bfloat16), argv[2], argv[4], n_heads=h,
        chunk=None, sub=None, interpret=True, conv=taps,
    )
    # the cell's call: 64-row chunks, bfloat16 states
    assert (plan.chunk, plan.sub, plan.taps) == (64, 8, 4 if conv else 0)
    assert plan.state_bytes == b * h * d * d * 2


def _kernel_calls(conv, out_norm=None):
    """The traced ``pallas_call`` equations of forward + backward, by name."""
    from horovod_tpu.analysis.jaxpr_walk import _sub_jaxprs_generic

    argv, weights = operands(1, 32, 1, 16)
    traced = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kda_attention(
        *a, n_heads=1, conv=conv, out_norm=out_norm, use_kernel=True,
        chunk=16, sub=8,
    ) * weights)))(*argv)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in _sub_jaxprs_generic(eqn):
                yield from walk(sub)

    return {e.params["name"]: e for e in walk(traced.jaxpr)
            if e.primitive.name == "pallas_call"}


@NORM
@CONV
def test_kernels_are_named_for_the_trace_and_carry_no_scope(conv, norm):
    calls = _kernel_calls(taps_for(1, 16) if conv else None,
                          EPS if norm else None)
    assert sorted(calls) == ["hvd_kda_bwd", "hvd_kda_fwd"]
    for e in calls.values():
        stack = str(e.source_info.name_stack)
        assert "attn_layout" not in stack and "kda_conv" not in stack
    # q, k, v, g, beta (the backward: the states and dO too); with taps
    # three halo blocks and three tap blocks more, and the taps' partial
    # gradients beside dq, dk, dv, dg, dbeta; with the exit norm 1 / rms
    # beside out and the states, and it and out back into the backward
    more = 6 if conv else 0
    assert len(calls["hvd_kda_fwd"].invars) == 5 + more
    assert len(calls["hvd_kda_fwd"].outvars) == 2 + norm
    assert len(calls["hvd_kda_bwd"].invars) == 7 + more + 2 * norm
    assert len(calls["hvd_kda_bwd"].outvars) == 5 + more // 2
    if norm:  # [B, H, 1, S_pad] float32, as dbeta leaves
        assert (calls["hvd_kda_fwd"].outvars[2].aval.shape
                == calls["hvd_kda_bwd"].outvars[4].aval.shape == (1, 1, 1, 32))


def _traced_grad(**kw):
    """The jaxpr, as text, of the gradient in q, k, v, g, beta of a call at
    2 x 200 (padded) x 2 heads of 128, bfloat16 operands."""
    x = lambda w, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 200, w), dtype
    )
    shapes = (x(256), x(256), x(256), x(256, jnp.float32),
              x(2, jnp.float32))
    return str(jax.make_jaxpr(jax.grad(lambda *a: kda_attention(
        *a, n_heads=2, use_kernel=True, **kw
    ).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))(*shapes))


def test_a_call_without_taps_traces_what_it_traced():
    """``conv=None`` is the call without the argument, equation for
    equation, compiled or interpreted (the parent's jaxprs themselves were
    compared when the argument came: CHANGES.md, PR 43)."""
    for interpret in (False, True):
        assert _traced_grad(interpret=interpret) == _traced_grad(
            interpret=interpret, conv=None
        )


@CONV
def test_a_call_without_the_exit_norm_traces_what_it_traced(conv):
    """``out_norm=None`` is the call without the argument, equation for
    equation, with and without taps, compiled or interpreted (the parent's
    jaxprs themselves were compared when the argument came: CHANGES.md,
    PR 45); with it the traced program is another."""
    for interpret in (False, True):
        call = dict(interpret=interpret)
        if conv:
            call["conv"] = taps_for(2, 128)
        assert _traced_grad(**call) == _traced_grad(**call, out_norm=None)
        assert _traced_grad(**call) != _traced_grad(**call, out_norm=EPS)


@pytest.mark.parametrize("moved", ["q", "v"])
def test_a_blocks_border_is_causal(moved):
    """Two grid steps of 128 rows. Moving ``q~`` at row 127, the first
    block's last, moves the output at rows 127..130 (the four rows whose
    convolution reaches it, three of them in the NEXT block) and at no
    other: q never enters the state. Moving ``v~`` there, with ``beta`` 0
    at that row so that its own value is never written, moves nothing up
    to row 127 and, through rows 128..130's values and the state, what
    follows."""
    b, s, h, d = 1, 256, 1, 16
    (q, k, v, g, beta), _ = operands(b, s, h, d, seed=8)
    taps = taps_for(h, d)
    beta = beta.at[:, 127].set(0.0)
    run = lambda q, v: np.asarray(kda_attention(  # noqa: E731
        q, k, v, g, beta, n_heads=h, conv=taps, use_kernel=True, chunk=16,
        sub=8,
    ))
    plan = kda_kernels._plan(q, v, beta, n_heads=h, chunk=16, sub=8,
                             interpret=True, conv=taps)
    assert (plan.block, plan.s_pad) == (128, 256)
    base = run(q, v)
    if moved == "q":
        changed = np.abs(run(q.at[0, 127].add(1.0), v) - base).max(axis=-1)[0]
        assert (changed[127:131] > 1e-4).all()
        assert (np.delete(changed, range(127, 131)) == 0).all()
    else:
        changed = np.abs(run(q, v.at[0, 127].add(1.0)) - base).max(axis=-1)[0]
        assert (changed[:128] == 0).all()
        assert (changed[128:131] > 1e-4).all() and (changed[131:] > 0).any()


@pytest.mark.parametrize("bad,match", [
    (dict(use_kernel=False), "recurrence path"),
    (dict(taps=KdaConv(*[jnp.zeros((4, 8))] * 3)), "conv taps"),
    (dict(taps=KdaConv(*[jnp.zeros((10, 16))] * 3)), "1 to 9 taps"),
    (dict(chunk=8), "multiple of 16"),
    (dict(use_kernel=False, taps=None, out_norm=EPS), "normalise the result"),
], ids=["recurrence", "widths", "too-many", "short-block",
        "recurrence-out-norm"])
def test_taps_that_do_not_fit_are_refused(bad, match):
    """And the exit norm off the kernel path, as the taps are."""
    (q, k, v, g, beta), _ = operands(1, 8, 1, 16)
    with pytest.raises(ValueError, match=match):
        kda_attention(
            q, k, v, g, beta, n_heads=1,
            conv=bad.get("taps", taps_for(1, 16)),
            out_norm=bad.get("out_norm"),
            use_kernel=bad.get("use_kernel", True), chunk=bad.get("chunk", 16),
            sub=8,
        )
