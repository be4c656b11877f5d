"""The select kernel family (``ops/dsa_kernels.py`` and the flash kernels
under ``keep=``) through the Pallas interpreter, small sizes: each row's
threshold and kept set against a sort, rows shorter than ``top_k``, ties
and exact zeros; the masked kernels' output, ``lse`` and three gradients
against XLA under the same mask, with and without ``q_rotary``, K/V heads
shared; the index loss and the indexer's three gradients against
``jax.grad`` of the loss written out; the counters; a call without a mask
builds the kernels it built.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import rotary, rotary_tables
from horovod_tpu.obs import registry
from horovod_tpu.ops import dsa_kernels as dsa
from horovod_tpu.ops.pallas_kernels import QRotary, flash_attention_with_lse

H_I, D_I = 4, 8


def _indexer(b, s, seed=0, zeros=False):
    """Seeded ``(q_idx, k_idx, w)``; ``zeros``: half the queries score
    every key exactly 0 (their products all negative under the ReLU)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, H_I * D_I))
    k = jax.random.normal(ks[1], (b, s, D_I))
    w = jax.random.normal(ks[2], (b, s, H_I)) * 0.3
    if zeros:
        dead = jax.random.uniform(ks[3], (b, s, 1)) < 0.5
        q, k = jnp.where(dead, -jnp.abs(q), q), jnp.abs(k)
    return q, k, w


def _by_sort(q, k, w, top_k):
    """``(keep [b, keys, queries], tau)`` from a numpy sort of each row."""
    scores = np.asarray(dsa.index_scores(q, k, w))
    b, s, _ = scores.shape
    keep = np.zeros((b, s, s), np.int8)
    tau = np.full((b, s), -np.inf, np.float32)
    for i in range(b):
        for t in range(s):
            row = scores[i, t, :t + 1]
            if t + 1 >= top_k:
                tau[i, t] = np.sort(row)[::-1][top_k - 1]
            keep[i, :t + 1, t] = row >= tau[i, t]
    return keep, tau


@pytest.mark.parametrize(
    "b,s,top_k,blocks,zeros",
    [(2, 64, 16, (32, 32), False), (1, 96, 32, (32, 64), False),
     (1, 40, 64, (32, 32), False), (1, 128, 16, (32, 64), True),
     (1, 72, 1, (16, 16), False), (2, 64, 64, (64, 16), False)],
    ids=["two-blocks", "uneven-blocks", "shorter-than-top_k", "exact-zeros",
         "top-1-padded", "top_k-is-the-length"],
)
def test_threshold_and_kept_set_are_the_sorts(b, s, top_k, blocks, zeros):
    q, k, w = _indexer(b, s, zeros=zeros)
    keep, tau, lse = dsa.dsa_select(
        q, k, w, top_k=top_k, use_kernel=True, block_q=blocks[0],
        block_k=blocks[1],
    )
    want_keep, want_tau = _by_sort(q, k, w, top_k)
    assert keep.dtype == jnp.int8 and keep.shape == (b, s, s)
    np.testing.assert_array_equal(np.asarray(keep), want_keep)
    finite = np.isfinite(want_tau)
    np.testing.assert_array_equal(np.isfinite(np.asarray(tau)), finite)
    np.testing.assert_allclose(np.asarray(tau)[finite], want_tau[finite],
                               rtol=1e-6, atol=1e-6)
    scores = np.asarray(dsa.index_scores(q, k, w))
    kept = want_keep.swapaxes(1, 2) != 0
    want_lse = np.log(np.where(kept, np.exp(scores), 0.0).sum(-1))
    np.testing.assert_allclose(np.asarray(lse), want_lse, rtol=1e-5,
                               atol=1e-5)
    # rows with at least top_k entries keep top_k, or more where they tie
    per_row = want_keep.sum(axis=1)
    assert (per_row >= np.minimum(np.arange(s) + 1, top_k)).all()
    # (four heads under a ReLU leave exact zeros, which tie where the
    # threshold falls on them and are all kept)
    counted = b * dsa.kept_entries(s, top_k)
    if zeros:
        assert int(per_row.sum()) > 2 * counted
    else:
        assert counted <= int(per_row.sum()) <= counted + s


def test_xla_form_gives_the_kernels_selection():
    q, k, w = _indexer(2, 64, seed=5)
    by_kernel = dsa.dsa_select(q, k, w, top_k=16, use_kernel=True,
                               block_q=32, block_k=32)
    by_xla = dsa.dsa_select(q, k, w, top_k=16, use_kernel=False)
    np.testing.assert_array_equal(by_kernel[0], by_xla[0])
    np.testing.assert_array_equal(np.isinf(by_kernel[1]), np.isinf(by_xla[1]))
    np.testing.assert_allclose(by_kernel[2], by_xla[2], rtol=1e-5, atol=1e-5)


def test_ordered_image_keeps_the_floats_order():
    x = jnp.asarray([-np.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, np.inf],
                    jnp.float32)
    key = np.asarray(dsa._ordered(x))
    assert (np.diff(key) > 0).all() and key.min() > np.iinfo(np.int32).min
    np.testing.assert_array_equal(np.asarray(dsa._floats(dsa._ordered(x))),
                                  np.asarray(x))


def test_select_books_its_counters_from_length_and_top_k():
    reg = registry.always()
    names = ("dsa.calls", "dsa.entries.causal", "dsa.entries.kept",
             "dsa.mask_bytes")
    before = [reg.counter(n).get() for n in names]
    q, k, w = _indexer(2, 64)
    # the XLA form builds no kernel call and counts nothing
    jax.eval_shape(lambda *a: dsa.dsa_select(*a, top_k=16, use_kernel=False),
                   q, k, w)
    assert [reg.counter(n).get() for n in names] == before
    jax.eval_shape(lambda *a: dsa.dsa_select(*a, top_k=16, use_kernel=True),
                   q, k, w)
    booked = [reg.counter(n).get() - b for n, b in zip(names, before)]
    assert booked == [1, 2 * 64 * 65 // 2, 2 * (16 * 17 // 2 + 48 * 16),
                      2 * 64 * 64]


# -- the flash kernels under a mask -----------------------------------------


def _masked_attention(q, k, v, keep, h, h_kv, d, rotate):
    """XLA under the same mask: ``(out [b, s, h d], lse [b, h, s])``."""
    b, s, _ = q.shape
    q, k, v = (t.reshape(b, s, n, d) for t, n in
               ((q, h), (k, h_kv), (v, h_kv)))
    if rotate:
        q = rotary(q, theta=1e4, halves=True)
    k, v = (jnp.repeat(t, h // h_kv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(d)
    valid = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]) & (
        keep.swapaxes(1, 2)[:, None] != 0
    )
    scores = jnp.where(valid, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                     precision="highest")
    return out.reshape(b, s, h * d), jax.nn.logsumexp(scores, axis=-1)


@pytest.mark.parametrize("rotate", [False, True], ids=["plain", "q_rotary"])
@pytest.mark.parametrize(
    "s,blocks,h,h_kv", [(64, (32, 32), 4, 2), (96, (32, 64), 4, 1),
                        (40, (16, 16), 2, 2)],
    ids=["groups-of-2", "one-kv-head-padded", "own-kv-heads-padded"],
)
def test_masked_flash_matches_xla_under_the_same_mask(s, blocks, h, h_kv,
                                                      rotate):
    b, d = 2, 16
    ks = jax.random.split(jax.random.PRNGKey(s), 6)
    q = jax.random.normal(ks[0], (b, s, h * d))
    k = jax.random.normal(ks[1], (b, s, h_kv * d))
    v = jax.random.normal(ks[2], (b, s, h_kv * d))
    # a selection's own mask: scattered, causal, at least one entry a row
    keep, _, _ = dsa.dsa_select(*_indexer(b, s, seed=s), top_k=12,
                                use_kernel=False)
    table = QRotary(*rotary_tables(s, d, theta=1e4), halves=True)

    def kernels(q, k, v):
        return flash_attention_with_lse(
            q, k, v, causal=True, layout="bsm", n_heads=h, n_kv_heads=h_kv,
            block_q=blocks[0], block_k=blocks[1], keep=keep,
            q_rotary=table if rotate else None,
        )

    def xla(q, k, v):
        return _masked_attention(q, k, v, keep, h, h_kv, d, rotate)

    w_out = jax.random.normal(ks[3], (b, s, h * d))
    w_lse = jax.random.normal(ks[4], (b, h, s))
    weigh = lambda f: lambda *a: (  # noqa: E731
        lambda out, lse: (out * w_out).sum() + (lse * w_lse).sum()
    )(*f(*a))
    got, want = kernels(q, k, v), xla(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-5)
    got_grads = jax.grad(weigh(kernels), (0, 1, 2))(q, k, v)
    want_grads = jax.grad(weigh(xla), (0, 1, 2))(q, k, v)
    for name, a, e in zip("qkv", got_grads, want_grads):
        np.testing.assert_allclose(a, e, rtol=5e-5, atol=5e-5, err_msg=name)


def test_mask_is_checked_and_names_the_kernels():
    q = jnp.zeros((1, 32, 2 * 16))
    call = lambda q=q, **kw: flash_attention_with_lse(  # noqa: E731
        q, q, q, layout="bsm", n_heads=2, **kw
    )
    keep = jnp.ones((1, 32, 32), jnp.int8)
    for bad in (dict(causal=False, keep=keep),
                dict(causal=True, keep=keep.astype(jnp.int32)),
                dict(causal=True, keep=keep[:, :16])):
        with pytest.raises(ValueError, match="keep"):
            call(**bad)
    masked = str(jax.make_jaxpr(jax.grad(
        lambda q: call(q, causal=True, keep=keep)[0].sum()
    ))(q))
    for name in ("hvd_flash_fwd_select", "hvd_flash_bwd_dkv_select",
                 "hvd_flash_bwd_dq_select"):
        assert name in masked
    # without a mask: the kernels and operands the call always built
    bare = str(jax.make_jaxpr(jax.grad(
        lambda q: call(q, causal=True)[0].sum()
    ))(q))
    assert bare == str(jax.make_jaxpr(jax.grad(
        lambda q: call(q, causal=True, keep=None)[0].sum()
    ))(q))
    assert "_select" not in bare and "i8[" not in bare


# -- the index loss ----------------------------------------------------------


def _written_out(q, k, q_idx, k_idx, w, keep, h, h_kv, d):
    """``L_I`` as the module's docstring has it, whole matrices."""
    b, s, _ = k_idx.shape
    kept = keep.swapaxes(1, 2) != 0
    qh = q.reshape(b, s, h, d)
    kh = jnp.repeat(k.reshape(b, s, h_kv, d), h // h_kv, axis=2)
    dots = jnp.einsum("bthd,bshd->bhts", qh, kh,
                      precision="highest") / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(kept[:, None], dots, -jnp.inf), axis=-1)
    target = jax.lax.stop_gradient(p.mean(axis=1))
    scores = jnp.einsum(
        "bth,bhts->bts", w, jax.nn.relu(jnp.einsum(
            "bthd,bsd->bhts", q_idx.reshape(b, s, H_I, D_I), k_idx,
            precision="highest",
        )), precision="highest",
    )
    logq = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
    safe = jnp.where(target > 0, target, 1.0)
    terms = jnp.where(target > 0,
                      target * (jnp.log(safe) - jnp.where(kept, logq, 0.0)),
                      0.0)
    return terms.sum() / (b * s)


@pytest.mark.parametrize(
    "s,top_k,blocks,h,h_kv", [(64, 16, (32, 32), 4, 2),
                              (96, 24, (32, 64), 4, 1),
                              (40, 8, (16, 16), 2, 2)],
    ids=["square-tiles", "wide-k-tiles", "padded"],
)
def test_index_loss_and_its_gradients_match_the_written_form(s, top_k, blocks,
                                                             h, h_kv):
    b, d = 2, 16
    ks = jax.random.split(jax.random.PRNGKey(s + 1), 3)
    q = jax.random.normal(ks[0], (b, s, h * d))
    k = jax.random.normal(ks[1], (b, s, h_kv * d))
    v = jax.random.normal(ks[2], (b, s, h_kv * d))
    q_idx, k_idx, w = _indexer(b, s, seed=3)
    keep, _, lse_idx = dsa.dsa_select(
        q_idx, k_idx, w, top_k=top_k, use_kernel=True, block_q=blocks[0],
        block_k=blocks[1],
    )
    _, lse = flash_attention_with_lse(
        q, k, v, causal=True, layout="bsm", n_heads=h, n_kv_heads=h_kv,
        keep=keep, block_q=blocks[0], block_k=blocks[1],
    )

    def by(use_kernel):
        return lambda q_idx, k_idx, w: dsa.dsa_index_loss(
            q, k, lse, q_idx, k_idx, w, keep, lse_idx, n_heads=h,
            n_kv_heads=h_kv, use_kernel=use_kernel, block_q=blocks[0],
            block_k=blocks[1],
        )

    written = lambda q_idx, k_idx, w: _written_out(  # noqa: E731
        q, k, q_idx, k_idx, w, keep, h, h_kv, d
    )
    want, want_grads = jax.value_and_grad(written, (0, 1, 2))(q_idx, k_idx, w)
    assert float(want) > 1e-2
    for use_kernel in (True, False):
        got, got_grads = jax.value_and_grad(by(use_kernel), (0, 1, 2))(
            q_idx, k_idx, w
        )
        assert float(got) == pytest.approx(float(want), rel=1e-5)
        for name, a, e in zip(("dq_idx", "dk_idx", "dw"), got_grads,
                              want_grads):
            np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-7,
                                       err_msg=f"{name} kernel={use_kernel}")
    # the target's operands take no gradient
    zero = jax.grad(lambda q, k: dsa.dsa_index_loss(
        q, k, lse, q_idx, k_idx, w, keep, lse_idx, n_heads=h,
        n_kv_heads=h_kv, use_kernel=True, block_q=blocks[0],
        block_k=blocks[1],
    ), (0, 1))(q, k)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in zero)


def test_index_loss_gradient_scales_with_its_cotangent():
    b, s, h, d = 1, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    q = jax.random.normal(ks[0], (b, s, h * d))
    k = jax.random.normal(ks[1], (b, s, h * d))
    q_idx, k_idx, w = _indexer(b, s, seed=4)
    keep, _, lse_idx = dsa.dsa_select(q_idx, k_idx, w, top_k=8,
                                      use_kernel=True, block_q=16, block_k=16)
    _, lse = flash_attention_with_lse(
        q, k, k, causal=True, layout="bsm", n_heads=h, keep=keep,
        block_q=16, block_k=16,
    )
    loss = lambda w, c: c * dsa.dsa_index_loss(  # noqa: E731
        q, k, lse, q_idx, k_idx, w, keep, lse_idx, n_heads=h, n_kv_heads=h,
        use_kernel=True, block_q=16, block_k=16,
    )
    one, three = (jax.grad(loss)(w, c) for c in (1.0, 3.0))
    np.testing.assert_allclose(three, 3.0 * one, rtol=1e-6)
