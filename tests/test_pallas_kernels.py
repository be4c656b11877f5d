"""Pallas flash attention: exactness vs the XLA reference implementation.

Mirrors the reference's numerical-parity test style (parallel tier,
``test/parallel/test_tensorflow.py`` — same op, multiple dtypes/configs,
tight tolerances).  On CPU the kernel runs in interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import dot_product_attention
from horovod_tpu.ops.pallas_kernels import (
    combine_blocks,
    flash_attention,
    flash_attention_latent,
    flash_attention_with_lse,
)


def _rand_qkv(rng, b, s, h, d, dtype=jnp.float32, skv=None):
    kq, kk, kv = jax.random.split(rng, 3)
    skv = s if skv is None else skv
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, skv, h, d), dtype)
    v = jax.random.normal(kv, (b, skv, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "b,s,h,d", [(2, 64, 4, 32), (1, 96, 2, 16)]
)
def test_flash_matches_reference(b, s, h, d, causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), b, s, h, d)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_cross_attention_uneven_kv():
    # Sq != Skv and Skv not a multiple of block_k (exercises padding mask).
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 2, 32, 2, 16, skv=40)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
    ref = dot_product_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 2, 64, 2, 32, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_lse_matches_logsumexp():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 48, 2, 16)
    _, lse = flash_attention_with_lse(q, k, v, block_q=16, block_k=16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(16)
    ref = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(lse, ref, atol=2e-5, rtol=2e-5)


def test_flash_offsets_shift_causal_mask():
    # With kv_offset = -S the whole K block is in the past → dense attention.
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 32, 2, 16)
    out = flash_attention_with_lse(
        q, k, v, causal=True, q_offset=32, kv_offset=0, block_q=16,
        block_k=16,
    )[0]
    ref = dot_product_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # Fully-future K block → rows have no valid keys → zero output, -inf lse.
    out2, lse2 = flash_attention_with_lse(
        q, k, v, causal=True, q_offset=0, kv_offset=32, block_q=16,
        block_k=16,
    )
    assert np.all(np.asarray(out2) == 0.0)
    assert np.all(np.isneginf(np.asarray(lse2)))


# name: (sq, skv, block or (block_q, block_k), head width, layout, whether
# ``lse`` gets a cotangent).  The padded cases leave padded q rows AND
# padded K/V columns in the one block a non-causal backward update takes
# (blocks of 16: 40 -> 48 rows, 56 -> 64 columns), under a non-zero
# ``g_lse``.  The cross cases give dK/dV's ``[d, block_k]`` accumulators a
# lane axis that is no multiple of 128 (one K/V block of 200 columns, the
# whole K/V; 136 in two blocks of 72, the second half padding) beside a q
# length that differs from it.  Heads of 128 take dK/dV's other form
# (``[block_k, d]`` accumulators, ``_dkv_streams_thin``).
_GRAD_CASES = {
    "even-bshd": (48, 48, 16, 16, "bshd", False),
    "padded-lse-bsm": (40, 56, 16, 16, "bsm", True),
    "padded-lse-bhsd": (40, 56, 16, 16, "bhsd", True),
    "cross-skv200-lse-bsm": (72, 200, (32, 256), 16, "bsm", True),
    "cross-skv200-lse-bhsd": (72, 200, (32, 256), 16, "bhsd", True),
    "cross-skv136-bk72-lse-bhsd": (200, 136, (64, 72), 16, "bhsd", True),
    "d128-padded-lse-bsm": (40, 56, 16, 128, "bsm", True),
}


@pytest.mark.parametrize("case", list(_GRAD_CASES))
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal, case):
    sq, skv, block, d, layout, with_lse = _GRAD_CASES[case]
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, sq, 2, d, skv=skv)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            total = jnp.sum(jnp.sin(out))
            return total + jnp.sum(lse ** 2) if with_lse else total
        return f

    def ref(q, k, v):
        if causal:
            return _offset_reference(q, k, v, 0, 0)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        return (dot_product_attention(q, k, v, causal=False),
                jax.scipy.special.logsumexp(scores, axis=-1))

    flash = lambda q, k, v: _flash_at(  # noqa: E731
        q, k, v, 0, 0, block, layout, causal=causal
    )
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    tol = 1e-4 if with_lse else 5e-5
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)


def test_combine_blocks_recovers_full_attention():
    # Split K/V in two halves, attend each, merge → dense result.
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 32, 2, 16, skv=64)
    o = jnp.zeros_like(q, dtype=jnp.float32)
    lse = jnp.full((1, 2, 32), -jnp.inf, jnp.float32)
    for half in range(2):
        ks = k[:, half * 32 : (half + 1) * 32]
        vs = v[:, half * 32 : (half + 1) * 32]
        oi, li = flash_attention_with_lse(q, ks, vs, block_q=16, block_k=16)
        o, lse = combine_blocks(o, lse, oi.astype(jnp.float32), li)
    ref = dot_product_attention(q, k, v, causal=False)
    np.testing.assert_allclose(o, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_attention_flash_matches_xla_ring(world8):
    # use_flash=True under shard_map reproduces the pure-XLA ring result.
    import horovod_tpu as hvd
    from horovod_tpu.parallel.sp import ring_attention

    n = 8
    b, s, h, d = 2, 8 * n, 4, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b, s, h, d)
    mesh = hvd.context().mesh
    sp = jax.sharding.PartitionSpec(None, hvd.WORLD_AXIS)

    for causal in (False, True):
        def run(use_flash, causal=causal):
            f = jax.shard_map(
                lambda q, k, v: ring_attention(
                    q, k, v, axis=hvd.WORLD_AXIS, causal=causal,
                    use_flash=use_flash, block_q=8, block_k=8,
                ),
                mesh=mesh,
                in_specs=(sp, sp, sp),
                out_specs=sp,
                check_vma=False,
            )
            return f(q, k, v)

        np.testing.assert_allclose(
            run(True), run(False), atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize(
    "d_model", [32, 128], ids=["head-major-d16", "packed-d64"]
)
def test_transformer_use_flash_matches_dense(d_model):
    """Heads of 16 take the head-major flash path, heads of 64 the packed
    one, whose out projection is one dot on the kernels' ``[B, S, H D]``
    output (``_packed_dot``): the same parameter tree, the same logits and
    the same gradient of every leaf as the XLA path."""
    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel

    kwargs = dict(
        vocab_size=128, max_len=32, d_model=d_model, n_heads=2, n_layers=1,
        d_ff=64, dtype=jnp.float32,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0, 128)
    # Pin the baseline to the dense path: use_flash=None auto-selects
    # flash on TPU, which would make this comparison flash-vs-flash.
    m1 = GPT2LMModel(GPT2Config(use_flash=False, **kwargs))
    m2 = GPT2LMModel(GPT2Config(use_flash=True, **kwargs))
    params = m1.init(jax.random.PRNGKey(9), tokens)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(m2.init, jax.random.PRNGKey(9), tokens)
    )
    w = jax.random.normal(jax.random.PRNGKey(10), (2, 32, 128))

    def grads(model):
        def loss(params):
            logits = model.apply(params, tokens)
            return jnp.sum(logits * w), logits
        with jax.default_matmul_precision("highest"):
            return jax.grad(loss, has_aux=True)(params)

    g1, logits1 = grads(m1)
    g2, logits2 = grads(m2)
    np.testing.assert_allclose(logits1, logits2, atol=1e-5, rtol=1e-5)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g1), jax.tree.leaves(g2)
    ):
        np.testing.assert_allclose(
            a, b, atol=2e-4, rtol=2e-4, err_msg=jax.tree_util.keystr(path)
        )


def test_flash_bsm_layout_matches_bhsd():
    """Packed [B,S,H*D] layout (heads sliced from the lane axis inside the
    kernel — the zero-relayout path the models use) matches the head-major
    layout exactly, forward and backward, causal and not."""
    from horovod_tpu.ops.pallas_kernels import flash_attention_with_lse

    B, S, H, D = 2, 64, 4, 16
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(B, S, H * D), jnp.float32)
    k = jnp.asarray(rs.randn(B, S, H * D), jnp.float32)
    v = jnp.asarray(rs.randn(B, S, H * D), jnp.float32)

    def f_bsm(q, k, v, causal):
        return flash_attention_with_lse(
            q, k, v, causal=causal, layout="bsm", n_heads=H,
            block_q=32, block_k=32,
        )

    def f_ref(q, k, v, causal):
        mv = lambda x: jnp.moveaxis(x.reshape(B, S, H, D), 2, 1)  # noqa: E731
        o, lse = flash_attention_with_lse(
            mv(q), mv(k), mv(v), causal=causal, layout="bhsd",
            block_q=32, block_k=32,
        )
        return jnp.moveaxis(o, 1, 2).reshape(B, S, H * D), lse

    for causal in (False, True):
        o1, l1 = f_bsm(q, k, v, causal)
        o2, l2 = f_ref(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=2e-5, atol=2e-5)
        loss1 = lambda *a: (  # noqa: E731
            f_bsm(*a, causal)[0].sum() + (f_bsm(*a, causal)[1] ** 2).sum()
        )
        loss2 = lambda *a: (  # noqa: E731
            f_ref(*a, causal)[0].sum() + (f_ref(*a, causal)[1] ** 2).sum()
        )
        g1 = jax.grad(loss1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss2, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def test_flash_bsm_requires_n_heads():
    from horovod_tpu.ops.pallas_kernels import flash_attention

    x = jnp.zeros((1, 16, 32), jnp.float32)
    with pytest.raises(ValueError, match="n_heads"):
        flash_attention(x, x, x, layout="bsm")


# ---------------------------------------------------------------------------
# Causal tile geometry: a causal call walks each copied block in compute
# tiles, skips those above the diagonal and runs those below it unmasked.
# Blocks of 16 / 32 rows give tiles of 8 / 16 in the interpreter, so these
# shapes hold two tiles per block edge and several blocks per sequence (a
# causal call widens its copied K/V block only by whole blocks that divide
# the K/V evenly, so 3 or 5 blocks stay 3 or 5).  The last two are the tile
# sizes the chip compiles, 128 and 256, run here in the interpreter.
# ---------------------------------------------------------------------------

# name: (sq, skv, block or (block_q, block_k), layout)
_GEOMETRIES = {
    "s96-b32-bsm": (96, 96, 32, "bsm"),
    "s160-b32-bhsd": (160, 160, 32, "bhsd"),
    "s96-b16-bsm": (96, 96, 16, "bsm"),
    "sq64-skv96-b32-bhsd": (64, 96, 32, "bhsd"),
    "s72-padded-b32-bsm": (72, 72, 32, "bsm"),
    # one padded q tile (rows 8..15 of a 16-row block, 12 real)
    "s12-padded-b16-bhsd": (12, 12, 16, "bhsd"),
    # the models' shape: K/V block twice the q block, 4 tiles wide
    "s128-bq32-bk64-bsm": (128, 128, (32, 64), "bsm"),
    # cross lengths, K/V no multiple of 128 and padded: a resident K/V
    # block of four tiles (every slab width of the ``[d, block_k]`` dK/dV
    # accumulators' lane slice), and one of two tiles in three blocks
    "sq80-skv200-bq16-bk128-bsm": (80, 200, (16, 128), "bsm"),
    "sq96-skv168-bq32-bk64-bhsd": (96, 168, (32, 64), "bhsd"),
    "s512-b256-tile128-bsm": (512, 512, 256, "bsm"),
    "s1024-b512-tile256-bsm": (1024, 1024, 512, "bsm"),
}
# name: (q_offset, kv_offset) as functions of (sq, skv)
_OFFSETS = {
    "model": lambda sq, skv: (0, 0),
    "past-hop": lambda sq, skv: (skv, 0),
    "future-hop": lambda sq, skv: (0, sq),
    "mid-tile": lambda sq, skv: (5, 0),
    # rows 0..9 see no key: real rows whose ``lse`` is -inf (at s 12 two of
    # them share the padded q tile with two rows that do see keys)
    "keys-ahead": lambda sq, skv: (0, 10),
}


def _offset_reference(q, k, v, q_offset, kv_offset):
    """(out, lse) of causal attention at global offsets, [B,S,H,D] in;
    rows with no visible key give zero output and -inf lse."""
    sq, skv = q.shape[1], k.shape[1]
    mask = (q_offset + jnp.arange(sq))[:, None] >= (
        kv_offset + jnp.arange(skv)
    )[None, :]
    seen = mask.any(axis=1)
    out = dot_product_attention(q, k, v, causal=False, mask=mask)
    out = jnp.where(seen[None, :, None, None], out, 0.0)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    lse = jax.scipy.special.logsumexp(
        jnp.where(mask, s, -jnp.inf), axis=-1
    )
    return out, lse


def _flash_at(q, k, v, q_offset, kv_offset, block, layout, causal=True):
    """flash (out, lse) for [B,S,H,D] inputs through ``layout``."""
    b, sq, h, d = q.shape
    block_q, block_k = block if isinstance(block, tuple) else (block, block)
    kwargs = dict(
        causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        block_q=block_q, block_k=block_k, layout=layout,
    )
    if layout == "bshd":
        return flash_attention_with_lse(q, k, v, **kwargs)
    if layout == "bsm":
        pack = lambda x: x.reshape(b, x.shape[1], h * d)  # noqa: E731
        out, lse = flash_attention_with_lse(
            pack(q), pack(k), pack(v), n_heads=h, **kwargs
        )
        return out.reshape(b, sq, h, d), lse
    mv = lambda x: jnp.moveaxis(x, 2, 1)  # noqa: E731
    out, lse = flash_attention_with_lse(mv(q), mv(k), mv(v), **kwargs)
    return jnp.moveaxis(out, 1, 2), lse


@pytest.mark.parametrize(
    "geometry,offsets",
    [
        (g, o) for g in _GEOMETRIES for o in _OFFSETS
        # the hops are covered at the small shapes
        if _GEOMETRIES[g][0] < 512 or o in ("model", "mid-tile")
    ],
)
def test_causal_tiles_match_reference(geometry, offsets):
    """Forward, lse and the three gradients across every tile class."""
    sq, skv, block, layout = _GEOMETRIES[geometry]
    q_offset, kv_offset = _OFFSETS[offsets](sq, skv)
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), 1, sq, 2, 16, skv=skv)

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
            return jnp.sum(jnp.sin(out)) + jnp.sum(lse ** 2)
        return f

    flash = lambda q, k, v: _flash_at(  # noqa: E731
        q, k, v, q_offset, kv_offset, block, layout
    )
    ref = lambda q, k, v: _offset_reference(  # noqa: E731
        q, k, v, q_offset, kv_offset
    )
    out, lse = flash(q, k, v)
    out_ref, lse_ref = ref(q, k, v)
    np.testing.assert_allclose(out, out_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "q_offset,kv_offset,sq,skv,block,tile",
    [
        (0, 0, 1024, 1024, 512, 128),
        (0, 0, 1024, 1024, 512, 256),
        (0, 0, 96, 96, 32, 8),
        (5, 0, 96, 96, 32, 8),
        (96, 0, 96, 96, 32, 8),
        (0, 96, 96, 96, 32, 8),
        (0, 0, 72, 72, 32, 8),
        (3, 7, 64, 90, 32, 16),
        (40, 0, 64, 200, 64, 16),
    ],
)
@pytest.mark.parametrize("window", [None, 5, 16, 40, 64, 1000])
def test_tile_classes_against_brute_force(q_offset, kv_offset, sq, skv,
                                          block, tile, window):
    """No tile classed interior holds a masked entry (or, under the
    backward's guard, a padded q row); no skipped tile, beyond the
    diagonal or left of a window's band, holds a valid one; the interior
    tiles lie together among the visited; the counts are the
    classifier's.  Windows smaller than a tile, of a whole number of tiles
    and blocks, of neither, and wider than the sequence."""
    from horovod_tpu.ops.pallas_kernels import _count_tiles, _tile_spans

    sq_pad = -(-sq // block) * block
    skv_pad = -(-skv // block) * block
    rows = np.arange(sq_pad)[:, None]
    cols = np.arange(skv_pad)[None, :]
    ahead = (q_offset + rows) - (kv_offset + cols)
    valid = (ahead >= 0) & (cols < skv)
    band = {}
    if window is not None:
        valid &= ahead < window
        band = {"window": window}
    nkt = block // tile
    for guard in (False, True):
        ok = valid & (rows < sq) if guard else valid
        visited = masked = 0
        for q0 in range(0, sq_pad, tile):
            for k0 in range(0, skv_pad, block):
                n_left, n_interior, n_visited = _tile_spans(
                    q_offset + q0, kv_offset + k0, skv - k0,
                    guard and q0 + tile > sq, tq=tile, tk=tile, nkt=nkt,
                    **band,
                )
                assert 0 <= n_left <= n_visited <= nkt
                assert 0 <= n_interior <= n_visited - n_left
                assert window is not None or n_left == 0
                visited += n_visited - n_left
                if n_interior < nkt:  # the q tile's slab is masked whole
                    masked += n_visited - n_left
                whole = [
                    ok[q0:q0 + tile, k0 + j * tile:k0 + (j + 1) * tile].all()
                    for j in range(nkt)
                ]
                # as many interior tiles as the brute force finds wholly
                # valid, or fewer (a tile of valid entries only beside
                # padding is run masked); never a tile that is not
                assert n_interior <= sum(whole)
                if n_interior:
                    first = whole.index(True)
                    assert all(whole[first:first + n_interior])
                    assert n_left <= first < first + n_interior <= n_visited
                for j in range(nkt):
                    if j < n_left or j >= n_visited:
                        assert not valid[
                            q0:q0 + tile, k0 + j * tile:k0 + (j + 1) * tile
                        ].any(), (q0, k0, j)
        total = (sq_pad // tile) * (skv_pad // tile)
        assert _count_tiles(
            q_offset, kv_offset, sq=sq, skv=skv, sq_pad=sq_pad,
            skv_pad=skv_pad, block_q=block, block_k=block, tq=tile,
            tk=tile, guard_q_pad=guard, **band,
        ) == (visited, masked, total - visited)


@pytest.mark.parametrize(
    "block,interpret,tile",
    [(512, False, 256), (256, False, 128), (128, False, 128),
     (200, False, 200), (64, True, 32), (32, True, 16), (16, True, 8),
     (40, True, 40)],
)
def test_compute_tile_rule(block, interpret, tile):
    from horovod_tpu.ops.pallas_kernels import _compute_tile

    assert _compute_tile(block, interpret) == tile


@pytest.mark.parametrize(
    "block_k,tk,skv_pad,resident",
    [(512, 256, 1024, 1024), (512, 256, 2048, 1024), (512, 256, 1536, 512),
     (512, 256, 512, 512), (256, 128, 1024, 512), (128, 128, 1024, 512),
     (1024, 256, 2048, 1024), (2048, 256, 2048, 2048), (32, 16, 96, 32),
     (16, 8, 96, 32), (64, 32, 128, 128)],
)
def test_resident_kv_rule(block_k, tk, skv_pad, resident):
    """Whole caller blocks, at most four tiles, dividing the K/V."""
    from horovod_tpu.ops.pallas_kernels import _resident_kv

    assert _resident_kv(block_k, tk, skv_pad) == resident
    assert skv_pad % resident == 0 and resident % block_k == 0


@pytest.mark.parametrize("block_k", [512, 1024])
@pytest.mark.parametrize("h", [1, 2, 4, 5, 6, 8, 10, 12, 16, 18, 20, 24])
def test_head_group_is_a_legal_packed_block(h, block_k):
    """Heads of 64 packed in the lane axis: a group is 128-lane aligned
    or all of the heads (3 of 6 heads, 192 lanes, is what Mosaic refused
    at ``block_k`` 1024)."""
    from horovod_tpu.ops.pallas_kernels import _head_group

    g = _head_group(h, 512, block_k, 64, True)
    assert h % g == 0 and ((g * 64) % 128 == 0 or g == h)
    # head-major blocks carry the group on a leading dim: no lane rule
    assert h % _head_group(h, 512, block_k, 64, False) == 0


def _flash_counters():
    from horovod_tpu.obs import registry

    reg = registry.always()
    return {
        name: reg.counter(name).get()
        for name in ("flash.tiles.visited", "flash.tiles.masked",
                     "flash.tiles.skipped", "flash.calls.dynamic_offsets")
    }


def _counted(fn, *args):
    before = _flash_counters()
    jax.eval_shape(fn, *args)
    after = _flash_counters()
    return tuple(after[k] - before[k] for k in before)


def test_flash_tile_counters_at_gpt2_shape():
    """Build-time counters: one batch element and head of the GPT-2 cell
    (s 1024, the whole K/V resident, 256 x 256 tiles: of the 16, 6 lie
    above the diagonal and are skipped, and each q tile takes its visited
    tiles as one slab that ends on the diagonal, so masked)."""
    def x(s):
        return (jax.ShapeDtypeStruct((1, s, 768), jnp.bfloat16),) * 3

    def fwd(causal, **blocks):
        return lambda q, k, v: flash_attention(
            q, k, v, causal=causal, layout="bsm", n_heads=12, **blocks
        )

    def grad(fn):
        return jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )

    assert _counted(fwd(True), *x(1024)) == (10, 10, 6, 0)
    assert _counted(fwd(False), *x(1024)) == (0, 0, 0, 0)
    # forward, dK/dV and dQ each book their own pallas_call
    assert _counted(grad(fwd(True)), *x(1024)) == (30, 30, 18, 0)
    # s 2048: two K/V blocks of 1024, so the last four q tiles find the
    # first block all interior and take it unmasked
    assert _counted(fwd(True), *x(2048)) == (36, 20, 28, 0)
    assert _counted(grad(fwd(True)), *x(2048)) == (108, 60, 84, 0)
    def ring_hop(q, k, v, r):
        return flash_attention_with_lse(
            q, k, v, causal=True, q_offset=r * 1024, kv_offset=0,
            layout="bsm", n_heads=12,
        )

    r = jax.ShapeDtypeStruct((), jnp.int32)
    assert _counted(ring_hop, *x(1024), r) == (0, 0, 0, 1)


# ---------------------------------------------------------------------------
# Orientation of the score tile, read from the traced kernels (no chip: the
# ``pallas_call``'s jaxpr at the two cells' block shapes).  The orientation
# is unconditional, so there is nothing to count at run time; these fail if
# a later edit turns a tile back.  The one form that follows a shape is
# which operand dK/dV's accumulating matmuls stream (the head width,
# ``_dkv_streams_thin``): read here at 64 and at 128, and counted at build
# time as ``flash.dkv.thin_streamed``.
# ---------------------------------------------------------------------------

# name: (sequence, causal): GPT-2's cell and BERT's MLM cell, one batch row
_CELL_SHAPES = {"gpt2-s1024-causal": (1024, True), "mlm-s512": (512, False)}


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    from horovod_tpu.analysis.jaxpr_walk import _sub_jaxprs_generic

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs_generic(eqn):
            yield from _walk(sub)


def _kernel_eqns(kernel, cell, heads=12):
    """Equations of the traced body of the flash kernel named ``kernel``
    inside forward + backward at ``cell``'s shape, as the chip compiles it
    (768 columns in ``heads`` heads, 12 of 64 as the models have them,
    packed, bf16, ``interpret=False``; nothing lowered)."""
    s, causal = _CELL_SHAPES[cell]
    x = jax.ShapeDtypeStruct((1, s, 768), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, layout="bsm", n_heads=heads,
            interpret=False,
        ).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    calls = [
        e for e in _walk(traced.jaxpr)
        if e.primitive.name == "pallas_call" and e.params["name"] == kernel
    ]
    assert len(calls) == 1, (kernel, len(calls))
    return list(_walk(calls[0].params["jaxpr"]))


def _column_reshapes(eqns):
    """Reshapes into a ``[rows, 1]`` column (a lane vector relaid)."""
    return [
        e for e in eqns if e.primitive.name == "reshape"
        and e.outvars[0].aval.shape[-1] == 1
    ]


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_dkv_kernel_scores_keys_by_queries(cell):
    """dK/dV: the two score matmuls a head are ``K Qᵀ`` and ``V gᵀ``,
    ``[cols, 64] x [rows, 64]ᵀ`` (nothing for Mosaic to transpose); the two
    accumulating ones stream the thin operand, ``gᵀ p`` and ``Qᵀ ds``: the
    ``[rows, 64]`` tile on the left, contracted on dimension 0, against the
    score-sized ``[cols, rows]`` ``pᵀ`` / ``dsᵀ`` contracted on dimension 1,
    into ``[64, cols]``.  The only transposes are of those ``[64, block_k]``
    accumulators, two a head where the K/V block is written, and no row
    statistic is relaid from its stored lane vector into a ``[rows, 1]``
    column."""
    causal = _CELL_SHAPES[cell][1]
    rows = 256 if causal else 512  # the q tile
    widths = (256, 512, 768, 1024) if causal else (512,)  # K/V slabs
    eqns = _kernel_eqns("hvd_flash_bwd_dkv", cell)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert dots and len(dots) % 4 == 0
    scores, accumulating = [], []
    for dot in dots:
        (lhs_contract, rhs_contract), _ = dot.params["dimension_numbers"]
        lhs, rhs = (v.aval.shape for v in dot.invars)
        if tuple(lhs_contract) == (1,):
            scores.append(dot)
            assert tuple(rhs_contract) == (1,), dot
            assert lhs[0] in widths and lhs[1] == 64, (lhs, rhs)
            assert rhs == (rows, 64), (lhs, rhs)
        else:
            accumulating.append(dot)
            assert tuple(lhs_contract) == (0,), dot
            assert tuple(rhs_contract) == (1,), dot
            assert lhs == (rows, 64), (lhs, rhs)
            assert rhs[0] in widths and rhs[1] == rows, (lhs, rhs)
            assert dot.outvars[0].aval.shape == (64, rhs[0])
            assert dot.outvars[0].aval.dtype == jnp.float32
    assert len(scores) == len(accumulating) == len(dots) // 2
    turned = [
        tuple(e.invars[0].aval.shape) for e in eqns
        if e.primitive.name == "transpose"
    ]
    # dKᵀ and dVᵀ, once per head of the program's group
    assert set(turned) == {(64, widths[-1])}, turned
    assert len(turned) % 2 == 0 and 12 % (len(turned) // 2) == 0, turned
    assert not _column_reshapes(eqns)


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_dkv_kernel_at_heads_of_128_streams_the_scores(cell):
    """Heads as wide as the lanes (6 x 128, the same 768 columns): ``pᵀ g``
    is a full-width pass, so dK/dV keeps ``[cols, 128]`` accumulators: all
    four matmuls a head contract dimension 1 of their left operand, the
    accumulating ones ``[cols, rows] x [rows, 128]``, and the body holds no
    transpose (measured: the turned form is 10% slower there at s 1024
    causal, PERF.md PR 34)."""
    causal = _CELL_SHAPES[cell][1]
    rows = 256 if causal else 512
    eqns = _kernel_eqns("hvd_flash_bwd_dkv", cell, heads=6)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert dots and len(dots) % 4 == 0
    accumulating = 0
    for dot in dots:
        (lhs_contract, rhs_contract), _ = dot.params["dimension_numbers"]
        lhs, rhs = (v.aval.shape for v in dot.invars)
        assert tuple(lhs_contract) == (1,), dot
        if tuple(rhs_contract) == (0,):  # pᵀ or dsᵀ against g or Q
            accumulating += 1
            assert lhs[1] == rows and rhs == (rows, 128), (lhs, rhs)
    assert accumulating == len(dots) // 2
    assert not [e for e in eqns if e.primitive.name == "transpose"]
    assert not _column_reshapes(eqns)


@pytest.mark.parametrize(
    "heads,d,layout,booked",
    [(12, 64, "bsm", 1), (6, 128, "bsm", 0), (8, 96, "bsm", 1),
     (2, 32, "bhsd", 1), (1, 256, "bhsd", 0)],
    ids=["12x64", "6x128", "8x96", "bhsd-d32", "bhsd-d256"],
)
def test_dkv_thin_streamed_counter_follows_head_width(
        heads, d, layout, booked):
    """Build-time counter ``flash.dkv.thin_streamed``: one per backward
    built whose heads are narrower than the 128 lanes, none at 128 and
    over; the forward alone books nothing."""
    from horovod_tpu.obs import registry

    counter = registry.always().counter("flash.dkv.thin_streamed")
    x = jax.ShapeDtypeStruct(
        (1, 256, heads * d) if layout == "bsm" else (1, heads, 256, d),
        jnp.bfloat16,
    )

    def fwd(q, k, v):
        return flash_attention(
            q, k, v, causal=True, layout=layout,
            n_heads=heads if layout == "bsm" else 0,
        ).astype(jnp.float32).sum()

    before = counter.get()
    jax.eval_shape(fwd, x, x, x)
    assert counter.get() == before
    jax.eval_shape(jax.grad(fwd, argnums=(0, 1, 2)), x, x, x)
    assert counter.get() - before == booked


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
@pytest.mark.parametrize(
    "kernel,matmuls", [("hvd_flash_fwd", 2), ("hvd_flash_bwd_dq", 3)]
)
def test_fwd_and_dq_kernels_keep_rows_on_lanes(kernel, matmuls, cell):
    """The two kernels that accumulate over q rows: nothing kept per q row
    is ever a ``[rows, 1]`` column, the only left operand a matmul
    contracts on dimension 0 (so the only one Mosaic must transpose) is a
    thin ``[cols, 64]`` K or V tile against the ``[cols, rows]`` scores,
    and the only transposes are of the ``[64, block_q]`` accumulators,
    once per head where the q block is written, and in dQ of the
    ``[block_q, 128]`` float32 products ``g * out`` of two heads that share
    a lane tile, once per pair where the q block arrives ("Δ at the door":
    the sums then run over sublanes and the statistic lands rows-on-lanes;
    no lane is shifted)."""
    eqns = _kernel_eqns(kernel, cell)
    assert not _column_reshapes(eqns)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert dots and len(dots) % matmuls == 0
    for dot in dots:
        (lhs_contract, rhs_contract), _ = dot.params["dimension_numbers"]
        lhs, rhs = (v.aval.shape for v in dot.invars)
        if tuple(lhs_contract) == (0,):
            assert lhs[1] == 64 and tuple(rhs_contract) == (0,), (lhs, rhs)
            assert rhs[0] == lhs[0] and rhs[1] in (256, 512), (lhs, rhs)
    turned = [
        tuple(e.invars[0].aval.shape) for e in eqns
        if e.primitive.name == "transpose"
    ]
    # one per head of the program's group (dQ: and one of the products)
    heads = turned.count((64, 512))
    assert heads and 12 % heads == 0, turned
    pairs = heads // 2 if kernel == "hvd_flash_bwd_dq" else 0
    assert sorted(turned) == [(64, 512)] * heads + [(512, 128)] * pairs


# ---------------------------------------------------------------------------
# Two head widths: q / k heads ``d`` wide, v / out heads ``dv`` (latent
# attention: 192 and 128).  One kernel body serves both; with equal widths
# the traced kernels are what they were (the four cells' compiled HLO is
# byte-identical across PR 36).
# ---------------------------------------------------------------------------

# name: (q / k width, v width, layout, sequence, block, causal)
_SPLIT_CASES = {
    "24-16-bsm-causal": (24, 16, "bsm", 72, 32, True),
    "24-16-bshd-causal": (24, 16, "bshd", 72, 32, True),
    "24-16-bhsd-padded": (24, 16, "bhsd", 40, 16, False),
    "16-24-bsm-wider-v": (16, 24, "bsm", 48, 16, True),
    "192-128-bsm-causal": (192, 128, "bsm", 64, 32, True),
    "128-64-bsm": (128, 64, "bsm", 32, 16, False),
}


@pytest.mark.parametrize("case", list(_SPLIT_CASES))
def test_flash_split_widths_match_reference(case):
    """Forward, ``lse`` and the three gradients against
    ``dot_product_attention`` (interpreter), in every layout."""
    d, dv, layout, s, block, causal = _SPLIT_CASES[case]
    h = 2
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(keys[0], (1, s, h, d))
    k = jax.random.normal(keys[1], (1, s, h, d))
    v = jax.random.normal(keys[2], (1, s, h, dv))
    w = jax.random.normal(keys[3], (1, s, h, dv))

    def flash(q, k, v):
        if layout == "bsm":
            out, lse = flash_attention_with_lse(
                q.reshape(1, s, h * d), k.reshape(1, s, h * d),
                v.reshape(1, s, h * dv), causal=causal, layout="bsm",
                n_heads=h, block_q=block, block_k=block,
            )
            return out.reshape(1, s, h, dv), lse
        if layout == "bhsd":
            out, lse = flash_attention_with_lse(
                *(jnp.moveaxis(x, 1, 2) for x in (q, k, v)), causal=causal,
                layout="bhsd", block_q=block, block_k=block,
            )
            return jnp.moveaxis(out, 1, 2), lse
        return flash_attention_with_lse(
            q, k, v, causal=causal, block_q=block, block_k=block
        )

    def reference(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
        return (dot_product_attention(q, k, v, causal=causal),
                jax.scipy.special.logsumexp(scores, axis=-1))

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * w) + 0.1 * jnp.sum(lse ** 2), (out, lse)
        # one trace gives the outputs and the three gradients
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)

    with jax.default_matmul_precision("highest"):
        got, (out, lse) = loss(flash)(q, k, v)
        want, (ref_out, ref_lse) = loss(reference)(q, k, v)
    assert out.shape == (1, s, h, dv)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def _split_kernel_eqns(kernel, d, dv, heads=4, s=1024):
    """Traced body of ``kernel`` in forward + backward of a causal packed
    call at q / k width ``d`` and v width ``dv``, as the chip compiles it."""
    wide = jax.ShapeDtypeStruct((1, s, heads * d), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((1, s, heads * dv), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=True, layout="bsm", n_heads=heads,
            interpret=False,
        ).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        wide, wide, narrow
    )
    calls = [
        e for e in _walk(traced.jaxpr)
        if e.primitive.name == "pallas_call" and e.params["name"] == kernel
    ]
    assert len(calls) == 1, (kernel, len(calls))
    return list(_walk(calls[0].params["jaxpr"]))


def test_dkv_kernel_at_192_and_128_streams_the_scores():
    """Latent attention's widths, both at or over the lanes: dK/dV keeps
    ``[cols, width]`` accumulators for both (dK 192 wide, dV 128), every
    matmul contracts dimension 1 of its left operand, and the body holds
    no transpose and no ``[rows, 1]`` column."""
    eqns = _split_kernel_eqns("hvd_flash_bwd_dkv", 192, 128)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert dots and len(dots) % 4 == 0
    widths = []
    for dot in dots:
        (lhs_contract, rhs_contract), _ = dot.params["dimension_numbers"]
        lhs, rhs = (v.aval.shape for v in dot.invars)
        assert tuple(lhs_contract) == (1,), dot
        if tuple(rhs_contract) == (0,):  # pᵀ g (128) or dsᵀ Q (192)
            assert lhs[1] == rhs[0] == 256, (lhs, rhs)
            widths.append(rhs[1])
        else:  # K Qᵀ over 192, V gᵀ over 128
            assert lhs[1] == rhs[1] and lhs[1] in (192, 128), (lhs, rhs)
    assert sorted(set(widths)) == [128, 192]
    assert widths.count(128) == widths.count(192) == len(dots) // 4
    assert not [e for e in eqns if e.primitive.name == "transpose"]
    assert not _column_reshapes(eqns)


def test_dkv_accumulators_take_their_form_from_their_own_width():
    """q / k at the lanes, v under them (128 / 64): dK accumulates
    ``dsᵀ Q`` into ``[cols, 128]`` and dV streams its thin operand,
    ``gᵀ p`` into ``[64, cols]``, turned back once a head."""
    eqns = _split_kernel_eqns("hvd_flash_bwd_dkv", 128, 64)
    outs = [
        tuple(e.outvars[0].aval.shape) for e in eqns
        if e.primitive.name == "dot_general"
        and tuple(e.params["dimension_numbers"][0][0]) in ((0,), (1,))
        and e.outvars[0].aval.shape[-1] != 256  # not a score tile
    ]
    assert {o for o in outs if o[0] == 64} and {o for o in outs if o[1] == 128}
    turned = {
        tuple(e.invars[0].aval.shape) for e in eqns
        if e.primitive.name == "transpose"
    }
    assert turned == {(64, 1024)}, turned


@pytest.mark.parametrize(
    "kernel,acc_width", [("hvd_flash_fwd", 128), ("hvd_flash_bwd_dq", 192)]
)
def test_fwd_and_dq_keep_rows_on_lanes_at_split_widths(kernel, acc_width):
    """The forward accumulates ``[dv, rows]`` and dQ ``[d, rows]``: the one
    transpose a head is of that accumulator (in dQ also of the head's
    ``[rows, dv]`` products ``g * out``, for the row statistic), and
    nothing kept per q row is a ``[rows, 1]`` column."""
    eqns = _split_kernel_eqns(kernel, 192, 128)
    assert not _column_reshapes(eqns)
    turned = {
        tuple(e.invars[0].aval.shape) for e in eqns
        if e.primitive.name == "transpose"
    }
    door = {(512, 128)} if kernel == "hvd_flash_bwd_dq" else set()
    assert turned == {(acc_width, 512)} | door, turned


def test_split_widths_counter_and_group():
    from horovod_tpu.obs import registry
    from horovod_tpu.ops.pallas_kernels import _head_group

    counter = registry.always().counter("flash.calls.split_widths")

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, layout="bsm", n_heads=4)

    x = lambda w: jax.ShapeDtypeStruct((1, 256, 4 * w), jnp.bfloat16)  # noqa: E731
    before = counter.get()
    jax.eval_shape(fwd, x(64), x(64), x(64))
    assert counter.get() == before
    jax.eval_shape(fwd, x(192), x(192), x(128))
    assert counter.get() == before + 1
    # 32 heads of 192 / 128, blocks of 512 x 1024: no group fits the 4 MB
    # budget, and one head of 192 is no legal packed block, so 2
    assert _head_group(32, 512, 1024, 192, True, 128) == 2
    # equal widths: what the one-width rule gave
    for h in (6, 12, 16):
        assert _head_group(h, 512, 1024, 64, True, 64) == _head_group(
            h, 512, 1024, 64, True
        )


# ---------------------------------------------------------------------------
# Latent attention's operands: the packed ``kv`` (``[k_nope | v]`` a head)
# and the one rotary key every head shares, through the same three kernels.
# ---------------------------------------------------------------------------

# name: (heads, n, r, dv, sq, skv, block, causal)
_LATENT_CASES = {
    "16+8-16-two-heads": (2, 16, 8, 16, 72, 72, 32, True),
    "16+8-16-one-head-padded": (1, 16, 8, 16, 40, 40, 16, True),
    "128+64-128-two-heads": (2, 128, 64, 128, 64, 64, 32, True),
    "128+64-128-one-head-padded": (1, 128, 64, 128, 40, 40, 16, True),
    "16+8-16-cross-skv-padded": (2, 16, 8, 16, 32, 40, 16, False),
}


def _built_keys(kv, k_rope, n):
    """K as it was built before the kernels read ``kv``: ``[k_nope | the
    shared key, broadcast to every head]``; and v."""
    b, s, h, _ = kv.shape
    k_rope = jnp.broadcast_to(k_rope[:, :, None], (b, s, h, k_rope.shape[-1]))
    return jnp.concatenate([kv[..., :n], k_rope], axis=-1), kv[..., n:]


@pytest.mark.parametrize("case", list(_LATENT_CASES))
def test_flash_latent_matches_reference_on_built_keys(case):
    """Forward, ``lse`` and the cotangents of q, ``kv`` and the shared key
    from one trace, against ``dot_product_attention`` on K built the old
    way (interpreter); groups of one head and of two."""
    h, n, r, dv, sq, skv, block, causal = _LATENT_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    q = jax.random.normal(keys[0], (1, sq, h, n + r))
    kv = jax.random.normal(keys[1], (1, skv, h, n + dv))
    k_rope = jax.random.normal(keys[2], (1, skv, r))
    w = jax.random.normal(keys[3], (1, sq, h, dv))

    def flash(q, kv, k_rope):
        out, lse = flash_attention_latent(
            q.reshape(1, sq, h * (n + r)), kv.reshape(1, skv, h * (n + dv)),
            k_rope, n_heads=h, causal=causal, block_q=block, block_k=block,
        )
        return out.reshape(1, sq, h, dv), lse

    def reference(q, kv, k_rope):
        k, v = _built_keys(kv, k_rope, n)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(n + r)
        if causal:
            scores = jnp.where(
                jnp.tril(jnp.ones((sq, skv), bool)), scores, -1e30
            )
        return (dot_product_attention(q, k, v, causal=causal),
                jax.scipy.special.logsumexp(scores, axis=-1))

    def loss(fn):
        def f(q, kv, k_rope):
            out, lse = fn(q, kv, k_rope)
            return jnp.sum(out * w) + 0.1 * jnp.sum(lse ** 2), (out, lse)
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)

    with jax.default_matmul_precision("highest"):
        got, (out, lse) = loss(flash)(q, kv, k_rope)
        want, (ref_out, ref_lse) = loss(reference)(q, kv, k_rope)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def _latent_calls(heads=4, s=1024, n=128, r=64, dv=128):
    """The three ``pallas_call`` equations of forward + backward of a causal
    latent call, as the chip compiles it, by kernel name."""
    shapes = [
        jax.ShapeDtypeStruct((1, s, width), jnp.bfloat16)
        for width in (heads * (n + r), heads * (n + dv), r)
    ]

    def loss(q, kv, k_rope):
        out, _ = flash_attention_latent(
            q, kv, k_rope, n_heads=heads, causal=True, interpret=False
        )
        return out.astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*shapes)
    calls = {
        e.params["name"]: e for e in _walk(traced.jaxpr)
        if e.primitive.name == "pallas_call"
    }
    assert sorted(calls) == [
        "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq", "hvd_flash_fwd"
    ]
    return calls


def test_latent_kernels_take_kv_and_the_shared_key_as_they_are():
    """At the cell's widths (128 + 64 / 128, groups of 2 heads): every
    kernel's K/V operands are the packed ``[1, s, H*256]`` and the shared
    ``[1, s, 64]`` themselves, no key of ``H*192`` columns is an operand
    or a result of anything but q and dq, dK/dV writes ``[dk_nope | dv]``
    in ``kv``'s layout and one float32 partial of the shared key's
    gradient a head group (K/V rows on the lanes, unpadded), and the one
    thing a body concatenates is a head's key tile, ``[cols, 128 + 64]``,
    in VMEM."""
    calls = _latent_calls()
    for name, call in calls.items():
        operands = [tuple(v.aval.shape) for v in call.invars]
        assert (1, 1024, 4 * 256) in operands, (name, operands)
        assert (1, 1024, 64) in operands, (name, operands)
        # q, and in the backward nothing else of that width
        assert operands.count((1, 1024, 4 * 192)) == 1, (name, operands)
        # g in both backward kernels, and out beside it in dQ alone
        assert operands.count((1, 1024, 4 * 128)) == {
            "hvd_flash_fwd": 0, "hvd_flash_bwd_dkv": 1, "hvd_flash_bwd_dq": 2,
        }[name], (name, operands)
        built = {
            tuple(tuple(v.aval.shape) for v in e.invars)
            for e in _walk(call.params["jaxpr"])
            if e.primitive.name == "concatenate"
        }
        assert built and all(
            len(tiles) == 2 and tiles[0][0] == tiles[1][0]
            and (tiles[0][1], tiles[1][1]) == (128, 64) for tiles in built
        ), (name, built)
    results = lambda name: [  # noqa: E731
        (tuple(v.aval.shape), v.aval.dtype) for v in calls[name].outvars
    ]
    assert results("hvd_flash_bwd_dkv") == [
        ((1, 1024, 4 * 256), jnp.bfloat16), ((1, 2, 64, 1024), jnp.float32)
    ]
    # dq, and the row statistic dK/dV reads, as ``lse`` is laid
    assert results("hvd_flash_bwd_dq") == [
        ((1, 1024, 4 * 192), jnp.bfloat16), ((1, 4, 8, 1024), jnp.float32)
    ]
    assert calls["hvd_flash_bwd_dq"].outvars[1] in calls[
        "hvd_flash_bwd_dkv"
    ].invars
    assert results("hvd_flash_fwd")[0] == ((1, 1024, 4 * 128), jnp.bfloat16)


def test_latent_dkv_accumulators_take_their_form_from_their_own_width():
    """dK's unshared part and dV, both at the lanes, keep ``[cols, 128]``
    (``dsᵀ·q_nope``, ``pᵀ·g``); the shared key's 64 columns stream their
    thin operand into ``[64, cols]``, which is written as it lies: the body
    holds no transpose."""
    body = list(_walk(_latent_calls()["hvd_flash_bwd_dkv"].params["jaxpr"]))
    outs = {
        tuple(e.outvars[0].aval.shape) for e in body
        if e.primitive.name == "dot_general"
        and 256 not in e.outvars[0].aval.shape[-1:]  # not a score tile
    }
    assert {o[1] for o in outs if o[0] != 64} == {128}, outs
    assert {o[0] for o in outs if o[1] != 128} == {64}, outs
    assert not [e for e in body if e.primitive.name == "transpose"]


def test_latent_kv_counter_counts_three_a_block():
    from horovod_tpu.obs import registry

    counter = registry.always().counter("flash.calls.latent_kv")
    split = registry.always().counter("flash.calls.split_widths")
    x = lambda w: jax.ShapeDtypeStruct((1, 256, w), jnp.bfloat16)  # noqa: E731

    def latent(q, kv, k_rope):
        out, _ = flash_attention_latent(q, kv, k_rope, n_heads=4, causal=True)
        return out.astype(jnp.float32).sum()

    def three(q, k, v):
        return flash_attention(
            q, k, v, causal=True, layout="bsm", n_heads=4
        ).astype(jnp.float32).sum()

    before, split_before = counter.get(), split.get()
    jax.eval_shape(jax.grad(three, argnums=(0, 1, 2)),
                   x(4 * 192), x(4 * 192), x(4 * 128))
    assert counter.get() == before
    jax.eval_shape(latent, x(4 * 192), x(4 * 256), x(64))
    assert counter.get() == before + 1  # the forward
    jax.eval_shape(jax.grad(latent, argnums=(0, 1, 2)),
                   x(4 * 192), x(4 * 256), x(64))
    assert counter.get() == before + 4  # + forward, dK/dV, dQ
    assert split.get() == split_before + 3  # forwards with dv != d


def test_flash_latent_refuses_shapes_that_are_not_its_layout():
    x = lambda w: jnp.zeros((1, 16, w))  # noqa: E731
    with pytest.raises(ValueError, match="H=2"):
        flash_attention_latent(x(48), x(64), jnp.zeros((1, 8, 8)), n_heads=2)
    with pytest.raises(ValueError, match="multiples of 64"):
        flash_attention_latent(
            x(48), x(64), x(8), n_heads=2, interpret=False
        )


# ---------------------------------------------------------------------------
# A window (band) and query groups (K/V heads shared by several query heads)
# ---------------------------------------------------------------------------


def _xla_attention(q, k, v, *, causal, window=None, keep=None):
    """``(out, lse)`` of attention written out: q ``[1, sq, h, d]``, k / v
    ``[1, skv, h_kv, d / dv]`` (a K/V head repeated for its query heads),
    ``keep`` the int8 mask keys by queries."""
    sq, h, d = q.shape[1:]
    skv, h_kv = k.shape[1:3]
    k, v = (jnp.repeat(x, h // h_kv, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    valid = np.ones((sq, skv), bool)
    if causal:
        ahead = np.arange(sq)[:, None] - np.arange(skv)[None, :]
        valid = (ahead >= 0) & (True if window is None else ahead < window)
    valid = jnp.asarray(valid)[None, None]
    if keep is not None:
        valid = valid & (keep.swapaxes(1, 2)[:, None] != 0)
    scores = jnp.where(valid, scores, -1e30)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]), v)
    return out, lse


def _in_layout(x, layout):
    if layout == "bsm":
        return x.reshape(x.shape[:2] + (-1,))
    return jnp.moveaxis(x, 2, 1) if layout == "bhsd" else x


def _from_layout(x, layout, d):
    if layout == "bsm":
        return x.reshape(x.shape[:2] + (-1, d))
    return jnp.moveaxis(x, 1, 2) if layout == "bhsd" else x


# name: (s, query heads, K/V heads, head width, window, (block_q, block_k),
# layout).  Compute tiles are half a block here (the interpreter's rule).
# Windows smaller than a tile (5 of 16), of a whole number of blocks (64 of
# 32), of neither (20, 33, 40); s no multiple of the block (100, 200);
# groups of 7 at width 128 and of 3, 2 and 1; a K/V block wider than the
# q block and the reverse, so both grids lose steps to the band.
_BAND_CASES = {
    "w20-7to1-bsm": (96, 7, 1, 16, 20, (32, 32), "bsm"),
    "w20-7to1-bhsd": (96, 7, 1, 16, 20, (32, 32), "bhsd"),
    "w20-7to1-bshd": (96, 7, 1, 16, 20, (32, 32), "bshd"),
    "w5-14to2-s100-bsm": (100, 14, 2, 16, 5, (32, 32), "bsm"),
    "w64-6to2-bk64-bsm": (128, 6, 2, 16, 64, (32, 64), "bsm"),
    "w33-4to4-s200-bq64-bsm": (200, 4, 4, 16, 33, (64, 32), "bsm"),
    "full-4to2-bsm": (96, 4, 2, 16, None, (32, 32), "bsm"),
    "full-6to2-bhsd": (96, 6, 2, 16, None, (32, 32), "bhsd"),
    "w40-14to2-d128-bsm": (96, 14, 2, 128, 40, (32, 32), "bsm"),
    "w40-14to2-d128-bhsd": (96, 14, 2, 128, 40, (32, 32), "bhsd"),
}


def _band_case_grads(case, heads_a_program=None):
    """Forward, ``lse`` and the three gradients of the kernels and of the
    reference, ONE trace each (a loss over both outputs)."""
    s, h, h_kv, d, window, block, layout = _BAND_CASES[case]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (1, s, h, d))
    k = jax.random.normal(kk, (1, s, h_kv, d))
    v = jax.random.normal(kv, (1, s, h_kv, d))
    packed = dict(n_heads=h, n_kv_heads=h_kv) if layout == "bsm" else {}

    def flash(q, k, v):
        out, lse = flash_attention_with_lse(
            *(_in_layout(t, layout) for t in (q, k, v)), causal=True,
            window=window, block_q=block[0], block_k=block[1],
            layout=layout, **packed,
        )
        return _from_layout(out, layout, d), lse

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(jnp.sin(out)) + jnp.sum(lse ** 2), (out, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    with jax.default_matmul_precision("highest"):
        (_, got), got_grads = loss(flash)(q, k, v)
        (_, want), want_grads = loss(
            lambda q, k, v: _xla_attention(
                q, k, v, causal=True, window=window
            )
        )(q, k, v)
    return got + got_grads, want + want_grads


@pytest.mark.parametrize("case", list(_BAND_CASES))
def test_flash_window_and_groups_match_reference(case):
    """Window x query groups against attention written out under the
    explicit band mask with repeated K/V: out, ``lse``, dQ, dK, dV."""
    got, want = _band_case_grads(case)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize(
    "case", ["w20-7to1-bsm", "w5-14to2-s100-bsm", "w64-6to2-bk64-bsm",
             "w40-14to2-d128-bhsd"],
)
def test_flash_groups_split_over_programs_match_reference(case, monkeypatch):
    """One query head a program (the VMEM budget at nothing): a K/V head's
    query heads take several programs, which follow one another along
    dK/dV's last grid axis with the accumulators running on."""
    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_GROUPED_VMEM", 0)
    got, want = _band_case_grads(case)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4, err_msg=name)


def _grouped_calls(window, *, s=512, h=14, h_kv=2, d=128, budget=None):
    """``name -> pallas_call equation`` of a grouped call's three kernels
    as they are traced for the chip (``interpret=False``; nothing runs)."""
    from horovod_tpu.ops import pallas_kernels as pk

    x = lambda heads: jax.ShapeDtypeStruct(  # noqa: E731
        (1, s, heads * d), jnp.bfloat16
    )

    def f(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, layout="bsm", n_heads=h,
            n_kv_heads=h_kv, interpret=False,
        ).astype(jnp.float32).sum()

    was = pk._GROUPED_VMEM
    pk._GROUPED_VMEM = was if budget is None else budget
    try:
        jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(
            x(h), x(h_kv), x(h_kv)
        )
    finally:
        pk._GROUPED_VMEM = was
    return {
        e.params["name"]: e
        for e in _walk(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize("budget,group", [(None, 7), (0, 1)])
def test_grouped_kv_block_is_one_head_and_no_wide_dk_exists(budget, group):
    """A program's K/V block is ONE K/V head's beside ``group`` query
    heads' q block, dK/dV leave the kernel at the K/V heads' width, and
    dK/dV's grid runs over the K/V heads with a head's programs along its
    last axis: neither K, V, dK nor dV exists at the query heads' width."""
    calls = _grouped_calls(128, budget=budget)
    assert sorted(calls) == ["hvd_flash_bwd_dkv_window",
                             "hvd_flash_bwd_dq_window", "hvd_flash_fwd_window"]
    for name, e in calls.items():
        widths = {v.aval.shape[-1] for v in e.invars if v.aval.ndim == 3}
        assert widths == {14 * 128, 2 * 128}, (name, widths)
        blocks = {
            tuple(getattr(x, "block_size", x) for x in b.block_shape)
            for b in e.params["grid_mapping"].block_mappings
        }
        # ``group`` query heads' q block beside ONE K/V head's block
        assert (1, 512, group * 128) in blocks, (name, blocks)
        assert (1, 512, 128) in blocks, (name, blocks)
        assert (1, 512, 2 * 128) not in blocks, (name, blocks)
    dkv = calls["hvd_flash_bwd_dkv_window"]
    assert [tuple(v.aval.shape) for v in dkv.outvars] == [
        (1, 512, 2 * 128), (1, 512, 2 * 128)
    ]
    grid = dkv.params["grid_mapping"].grid
    assert grid[1] == 2 and grid[3] % (7 // group) == 0, grid
    assert calls["hvd_flash_fwd_window"].params["grid_mapping"].grid[1] == (
        14 // group
    )


def test_window_drops_grid_steps_at_both_ends():
    """s 4096, blocks of 512 (K/V resident 1024), window 512: a q block
    reaches at most 2 of the 4 K/V blocks and a K/V block at most 4 of
    the 8 q blocks, and the grids are that long; the causal call's are
    4 and 8."""
    band = _grouped_calls(512, s=4096)
    full = _grouped_calls(None, s=4096)
    grid = lambda calls, name: tuple(  # noqa: E731
        calls[name].params["grid_mapping"].grid
    )
    assert grid(full, "hvd_flash_fwd") == (1, 2, 8, 4)
    assert grid(band, "hvd_flash_fwd_window") == (1, 2, 8, 2)
    assert grid(band, "hvd_flash_bwd_dq_window") == (1, 2, 8, 2)
    assert grid(full, "hvd_flash_bwd_dkv") == (1, 2, 4, 8)
    assert grid(band, "hvd_flash_bwd_dkv_window") == (1, 2, 4, 4)


@pytest.mark.parametrize("layout", ["bsm", "bhsd"])
def test_window_no_row_reaches_is_the_causal_call_bit_for_bit(layout):
    """``window >= S`` (static offsets) traces the causal call: the same
    jaxpr, the same kernels' names, the same bits."""
    q, k, v = (
        _in_layout(t, layout)
        for t in _rand_qkv(jax.random.PRNGKey(12), 1, 96, 2, 16)
    )
    packed = dict(n_heads=2) if layout == "bsm" else {}

    def f(window):
        def loss(q, k, v):
            out, lse = flash_attention_with_lse(
                q, k, v, causal=True, window=window, block_q=32,
                block_k=32, layout=layout, **packed,
            )
            return jnp.sum(jnp.sin(out)) + jnp.sum(lse ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    assert str(jax.make_jaxpr(f(96))(q, k, v)) == str(
        jax.make_jaxpr(f(None))(q, k, v)
    )
    wide, causal = f(4096)(q, k, v), f(None)(q, k, v)
    for a, b in zip(jax.tree.leaves(wide), jax.tree.leaves(causal)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # one position short of the sequence is a window
    assert "hvd_flash_fwd_window" in str(jax.make_jaxpr(f(95))(q, k, v))


def test_windowed_and_grouped_counters_count_a_kernel_each():
    from horovod_tpu.obs import registry

    reg = registry.always()
    names = ("flash.calls.windowed", "flash.calls.grouped_kv",
             "flash.tiles.visited", "flash.tiles.masked",
             "flash.tiles.skipped")
    x = lambda heads: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 2048, heads * 128), jnp.bfloat16
    )

    def counted(window, h_kv, grad):
        def f(q, k, v):
            return flash_attention(
                q, k, v, causal=True, window=window, layout="bsm",
                n_heads=14, n_kv_heads=h_kv,
            ).astype(jnp.float32).sum()

        before = [reg.counter(n).get() for n in names]
        jax.eval_shape(jax.grad(f, argnums=(0, 1, 2)) if grad else f,
                       x(14), x(h_kv), x(h_kv))
        return tuple(reg.counter(n).get() - b for n, b in zip(names, before))

    # s 2048 in 8 x 8 tiles of 256: 36 under the diagonal (see the GPT-2
    # shape's test); a window of 512 leaves each q tile 3 tiles, the
    # first two q tiles 1 and 2: 21, all masked, 43 skipped
    assert counted(None, 14, False) == (0, 0, 36, 20, 28)
    assert counted(512, 14, False) == (1, 0, 21, 21, 43)
    assert counted(512, 2, False) == (1, 1, 21, 21, 43)
    assert counted(None, 2, True) == (0, 3, 108, 60, 84)
    assert counted(512, 2, True) == (3, 3, 63, 63, 129)
    assert counted(4096, 2, False) == (0, 1, 36, 20, 28)  # no window at all


@pytest.mark.parametrize(
    "kwargs,match",
    [
        (dict(causal=False, window=8), "window=8 needs causal=True"),
        (dict(causal=True, window=0), "window=0 needs causal=True and"),
        (dict(causal=True, layout="bsm", n_heads=6, n_kv_heads=4),
         "n_heads % n_kv_heads != 0"),
        (dict(causal=True, layout="bhsd", n_kv_heads=3),
         "n_kv_heads=3 is for layout='bsm'"),
    ],
    ids=["window-without-causal", "window-zero", "heads-not-shared-evenly",
         "n-kv-heads-outside-bsm"],
)
def test_flash_refuses_a_window_or_groups_it_cannot_take(kwargs, match):
    """Plain ``ValueError``s with the shapes in the message."""
    x = jnp.zeros((1, 6, 16, 8) if kwargs.get("layout") == "bhsd"
                  else (1, 16, 48))
    if kwargs.get("layout") is None:
        x = jnp.zeros((1, 16, 6, 8))
    with pytest.raises(ValueError, match=match) as refused:
        flash_attention(x, x, x, **kwargs)
    assert str(tuple(x.shape)) in str(refused.value)


def test_flash_refuses_grouped_heads_off_the_lanes_when_compiled():
    """Compiled, a packed K/V block of one head has to fill the lanes."""
    q, kv = jnp.zeros((1, 16, 4 * 64)), jnp.zeros((1, 16, 2 * 64))
    with pytest.raises(ValueError, match="multiples of 128"):
        flash_attention(q, kv, kv, causal=True, layout="bsm", n_heads=4,
                        n_kv_heads=2, interpret=False)


@pytest.mark.parametrize(
    "ratio,d,block_q,group",
    [(7, 128, 512, 7), (7, 128, 2048, 1), (4, 128, 512, 4), (8, 64, 512, 8),
     (3, 64, 512, 2), (1, 64, 512, None)],
)
def test_head_group_lies_within_one_kv_heads_query_heads(ratio, d, block_q,
                                                         group):
    """With query groups a program's heads divide one K/V head's: 7 of 7 at
    the cell's blocks, 1 where 7 q blocks pass the budget; packed groups
    stay lane-legal (2 of 3 heads of 64 are not: 1... of 3 is not either,
    so all 3 only where they are all the heads).  A ratio of 1 is the
    rule it was."""
    from horovod_tpu.ops.pallas_kernels import _head_group

    h = 4 * ratio
    if group is None:
        assert _head_group(h, block_q, 1024, d, True, kv_ratio=1) == (
            _head_group(h, block_q, 1024, d, True)
        )
        return
    if (ratio, d) == (3, 64):
        # no divisor of 3 is 128-lane aligned at width 64 and 3 != h: one
        # head a program, which the entry refuses compiled
        assert _head_group(h, block_q, 1024, d, True, kv_ratio=ratio) == 1
        assert _head_group(h, block_q, 1024, d, False, kv_ratio=ratio) == 3
        return
    g = _head_group(h, block_q, 1024, d, True, kv_ratio=ratio)
    assert g == group and ratio % g == 0


# ---------------------------------------------------------------------------
# Rotary at the door (``q_rotary=``): q enters as its projection leaves it,
# the forward and dQ turn in VMEM, dK/dV reads the forward's turned q.
# ---------------------------------------------------------------------------

# name: (entry, heads, K/V heads, n, r, dv, sq, block, window, halves)
_ROTARY_CASES = {
    "latent-pairs-16+8-16": ("latent", 2, 2, 16, 8, 16, 72, 32, None, False),
    "latent-pairs-128+64-128": (
        "latent", 2, 2, 128, 64, 128, 64, 32, None, False),
    "latent-pairs-128+64-128-one-head-padded": (
        "latent", 1, 1, 128, 64, 128, 40, 16, None, False),
    "qkv-halves-16-groups-window": (
        "bsm", 4, 2, 0, 16, 16, 72, 32, 24, True),
    "qkv-halves-128-groups-window": (
        "bsm", 4, 2, 0, 128, 128, 64, 32, 24, True),
    "qkv-halves-16-groups-window-padded": (
        "bsm", 6, 2, 0, 16, 16, 50, 16, 20, True),
    "qkv-pairs-16-causal-head-major": (
        "bhsd", 2, 2, 0, 16, 16, 48, 16, None, False),
    "qkv-halves-last-8-of-24-cross": (
        "bshd", 2, 2, 16, 8, 24, 40, 16, None, True),
}


def _rotary_case(case, rotary_in_kernels):
    """Loss -> ((out, lse), (dq, dk, dv)) of one case, either the kernels
    rotating q (``q_rotary``) or ``rotary`` in front of the same call."""
    from horovod_tpu.models.transformer import rotary, rotary_tables
    from horovod_tpu.ops.pallas_kernels import QRotary

    entry, h, h_kv, n, r, dv, sq, block, window, halves = _ROTARY_CASES[case]
    d, theta = n + r, 1e4
    causal = "cross" not in case
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    q = jax.random.normal(keys[0], (1, sq, h, d))
    w = jax.random.normal(keys[3], (1, sq, h, dv))
    if entry == "latent":
        k = jax.random.normal(keys[1], (1, sq, h * (n + dv)))
        v = jax.random.normal(keys[2], (1, sq, r))
    else:
        k = jax.random.normal(keys[1], (1, sq, h_kv, d))
        v = jax.random.normal(keys[2], (1, sq, h_kv, dv))
    tables = QRotary(
        *rotary_tables(sq, r, theta=theta), halves=halves, start=n
    )

    def loss(q, k, v):
        kw = dict(causal=causal, block_q=block, block_k=block)
        if rotary_in_kernels:
            kw["q_rotary"] = tables
        else:
            q = jnp.concatenate([
                q[..., :n], rotary(q[..., n:], theta=theta, halves=halves)
            ], axis=-1)
        if entry == "latent":
            out, lse = flash_attention_latent(
                q.reshape(1, sq, h * d), k, v, n_heads=h, **kw
            )
            out = out.reshape(1, sq, h, dv)
        else:
            layout = lambda x: _in_layout(x, entry)  # noqa: E731
            out, lse = flash_attention_with_lse(
                layout(q), layout(k), layout(v), layout=entry, window=window,
                **(dict(n_heads=h, n_kv_heads=h_kv) if entry == "bsm" else {}),
                **kw,
            )
            out = _from_layout(out, entry, dv)
        return jnp.sum(out * w) + 0.1 * jnp.sum(lse ** 2), (out, lse)

    with jax.default_matmul_precision("highest"):
        grads, results = jax.jit(
            jax.grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
    return results, grads


@pytest.mark.parametrize("case", list(_ROTARY_CASES))
def test_flash_rotates_q_as_rotary_in_front_of_the_same_call(case):
    """out, ``lse``, dq, dk and dv (with the latent entry ``d[k_nope | v]``
    and the shared key's) from one trace, the kernels' own rotation against
    ``rotary`` + the same entry without tables: adjacent pairs on the last
    lanes of a latent head, halves with query groups under a window, padded
    lengths, every layout.  dq is the gradient of the UNROTATED q: the
    other side reaches it by autodiff through ``rotary``."""
    (out, lse), got = _rotary_case(case, True)
    (ref_out, ref_lse), want = _rotary_case(case, False)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def _rotary_calls(tables, *, latent, interpret=False):
    """The three ``pallas_call`` equations of forward + backward at the
    cells' widths (4 heads of 128 + 64 / 128 in pairs, or 14 on 2 K/V heads
    of 128 in halves under a window), by kernel name."""
    from horovod_tpu.models.transformer import rotary_tables
    from horovod_tpu.ops.pallas_kernels import QRotary

    s = 1024
    x = lambda w: jax.ShapeDtypeStruct((1, s, w), jnp.bfloat16)  # noqa: E731
    if latent:
        q_rotary = QRotary(*rotary_tables(s, 64, theta=32e6), start=128)
        shapes = (x(4 * 192), x(4 * 256), x(64))
    else:
        q_rotary = QRotary(*rotary_tables(s, 128, theta=1.5e6), halves=True)
        shapes = (x(14 * 128), x(2 * 128), x(2 * 128))
    kw = dict(q_rotary=q_rotary) if tables else {}

    def loss(q, k, v):
        if latent:
            out, _ = flash_attention_latent(
                q, k, v, n_heads=4, causal=True, interpret=interpret, **kw
            )
        else:
            out = flash_attention(
                q, k, v, causal=True, window=512, layout="bsm", n_heads=14,
                n_kv_heads=2, interpret=interpret, **kw
            )
        return out.astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*shapes)
    return traced, {
        e.params["name"].replace("_window", ""): e
        for e in _walk(traced.jaxpr) if e.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize("latent", [True, False], ids=["latent", "window"])
def test_rotary_turns_in_the_forward_and_dq_and_nowhere_else(latent):
    """With tables: the forward takes the ``[S, 2 r]`` table and writes a
    second array shaped like q, the turned q, which dK/dV and dQ read in
    q's place (dK/dV is the call it was: no table, no rotation); dQ takes
    the table and writes q's gradient in q's dtype.  The rotations are lane
    rolls in the forward's and dQ's bodies; between the kernels nothing
    touches q or dq."""
    traced, calls = _rotary_calls(True, latent=latent)
    _, plain = _rotary_calls(False, latent=latent)
    assert sorted(calls) == sorted(plain) == [
        "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq", "hvd_flash_fwd"
    ]
    r, q_shape = (64, (1, 1024, 768)) if latent else (128, (1, 1024, 1792))
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]  # noqa: E731
    rolls = lambda e: sum(  # noqa: E731
        1 for x in _walk(e.params["jaxpr"]) if x.primitive.name == "roll"
    )
    for name, call in calls.items():
        extra = [s for s in shapes(call.invars)
                 if s not in shapes(plain[name].invars)]
        assert extra == ([] if "dkv" in name else [(1024, 2 * r)]), name
        assert bool(rolls(call)) == ("dkv" not in name), name
        assert not rolls(plain[name])
    fwd, dq = calls["hvd_flash_fwd"], calls["hvd_flash_bwd_dq"]
    assert shapes(fwd.outvars).count(q_shape) == (
        1 + shapes(plain["hvd_flash_fwd"].outvars).count(q_shape)
    )
    turned_q = fwd.outvars[-1]
    for name in ("hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"):
        assert turned_q in calls[name].invars, name
    assert str(calls["hvd_flash_bwd_dkv"].params["jaxpr"]) == str(
        plain["hvd_flash_bwd_dkv"].params["jaxpr"]
    )
    dq_out, _ = dq.outvars  # and the row statistic
    assert dq_out.aval.dtype == jnp.bfloat16
    assert dq_out in traced.jaxpr.outvars  # as it leaves the kernel
    # a head of the program's (2 / 7): pairs a roll either way, halves one
    # roll by half the width
    assert rolls(dq) == rolls(fwd) == (2 * 2 if latent else 7)


def test_a_call_without_tables_traces_what_it_traced():
    """``q_rotary=None`` is the call without the argument, equation for
    equation, in both entries and with the kernels compiled or interpreted
    (the parent's jaxprs themselves were compared when the argument came:
    CHANGES.md, PR 41)."""
    x = lambda w: jax.ShapeDtypeStruct((2, 256, w), jnp.bfloat16)  # noqa: E731

    def traced(entry, **kw):
        def loss(q, k, v):
            out = entry(q, k, v, causal=True, n_heads=4, **kw)
            out = out[0] if isinstance(out, tuple) else out
            return out.astype(jnp.float32).sum()
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))

    for interpret in (False, True):
        for entry, kw, shapes in (
            (flash_attention, dict(layout="bsm"), (x(256), x(256), x(256))),
            (flash_attention_latent, {}, (x(768), x(1024), x(64))),
        ):
            kw = dict(kw, interpret=interpret)
            assert str(traced(entry, **kw)(*shapes)) == str(
                traced(entry, q_rotary=None, **kw)(*shapes)
            )


def test_rotary_q_counter_counts_the_kernels_that_turn():
    from horovod_tpu.obs import registry

    counter = registry.always().counter("flash.calls.rotary_q")

    def counted(tables, latent):
        before = counter.get()
        _rotary_calls(tables, latent=latent, interpret=True)
        return counter.get() - before

    assert counted(False, True) == counted(False, False) == 0
    assert counted(True, True) == 2 == counted(True, False)  # forward, dQ


@pytest.mark.parametrize("bad,match", [
    (dict(rows=71), "Sq=72"), (dict(start=20), "head width"),
    (dict(compiled=True), "multiples of 64"),
])
def test_rotary_tables_that_do_not_fit_q_are_refused(bad, match):
    from horovod_tpu.models.transformer import rotary_tables
    from horovod_tpu.ops.pallas_kernels import QRotary

    q = jnp.zeros((1, 72, 2 * (64 if "compiled" in bad else 24)))
    tables = QRotary(
        *rotary_tables(bad.get("rows", 72), 8, theta=1e4),
        start=bad.get("start", 16),
    )
    with pytest.raises(ValueError, match=match):
        flash_attention(
            q, q, q, causal=True, layout="bsm", n_heads=2, q_rotary=tables,
            interpret=not bad.get("compiled", False),
        )


# ---------------------------------------------------------------------------
# Norm at the door (``q_norm=``): q enters as its PROJECTION leaves it, the
# forward norms each head in VMEM ahead of the turn, dQ leaves as the raw q's
# gradient with the scale's beside it, dK/dV reads the forward's q.
# ---------------------------------------------------------------------------

# name: (layout, heads, K/V heads, head width, sq, block, rotary, keep)
_NORM_CASES = {
    "halves-16-groups": ("bsm", 4, 2, 16, 72, 32, "halves", False),
    "halves-128-groups-keep": ("bsm", 4, 2, 128, 64, 32, "halves", True),
    "halves-16-groups-keep-padded": ("bsm", 6, 2, 16, 50, 16, "halves", True),
    "halves-128-padded": ("bsm", 2, 2, 128, 40, 16, "halves", False),
    "pairs-16-head-major": ("bhsd", 2, 2, 16, 48, 16, "pairs", False),
    "no-rotary-16-groups": ("bsm", 4, 2, 16, 48, 16, None, False),
    "no-rotary-128-keep-bshd": ("bshd", 2, 1, 128, 40, 16, None, True),
}
_NORM_EPS = 1e-5


def _norm_case(case, norm_in_kernels):
    """Loss -> ((out, lse, q_seen), (dq, dk, dv, dscale)) of one case, either
    the kernels norming (and rotating) q or ``RMSNorm`` (+ ``rotary``) in
    front of the same call."""
    from horovod_tpu.models.transformer import (
        RMSNorm, rotary, rotary_tables,
    )
    from horovod_tpu.ops.pallas_kernels import QNorm, QRotary

    layout, h, h_kv, d, sq, block, turn, masked = _NORM_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(29), 6)
    q = jax.random.normal(keys[0], (1, sq, h, d))
    k = jax.random.normal(keys[1], (1, sq, h_kv, d))
    v = jax.random.normal(keys[2], (1, sq, h_kv, d))
    w = jax.random.normal(keys[3], (1, sq, h, d))
    scale = 1.0 + 0.3 * jax.random.normal(keys[4], (d,))
    keep = None
    if masked:  # keys by queries; the diagonal kept, so every row sees one
        keep = ((jax.random.uniform(keys[5], (1, sq, sq)) < 0.5)
                | jnp.eye(sq, dtype=bool)).astype(jnp.int8)
    halves = turn == "halves"

    def loss(q, k, v, scale):
        kw = dict(causal=True, block_q=block, block_k=block, keep=keep,
                  return_q=True)
        if norm_in_kernels:
            kw["q_norm"] = QNorm(scale, _NORM_EPS)
            if turn:
                kw["q_rotary"] = QRotary(
                    *rotary_tables(sq, d, theta=1e4), halves=halves
                )
        else:
            q = RMSNorm(_NORM_EPS, jnp.float32).apply(
                {"params": {"scale": scale}}, q
            )
            if turn:
                q = rotary(q, theta=1e4, halves=halves)
        shaped = lambda x: _in_layout(x, layout)  # noqa: E731
        out, lse, q_seen = flash_attention_with_lse(
            shaped(q), shaped(k), shaped(v), layout=layout,
            **(dict(n_heads=h, n_kv_heads=h_kv) if layout == "bsm" else {}),
            **kw,
        )
        out = _from_layout(out, layout, d)
        q_seen = _from_layout(q_seen, layout, d)
        # q_seen is a constant: its term must add nothing to dq
        return (jnp.sum(out * w) + 0.1 * jnp.sum(lse ** 2)
                + jnp.sum(q_seen * w)), (out, lse, q_seen)

    with jax.default_matmul_precision("highest"):
        grads, results = jax.jit(
            jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)
        )(q, k, v, scale)
    return results, grads


@pytest.mark.parametrize("case", list(_NORM_CASES))
def test_flash_norms_q_as_rmsnorm_in_front_of_the_same_call(case):
    """out, ``lse``, the q the scores saw, dq, dk, dv and the scale's
    gradient from one trace, the kernels' own norm (and rotation) against
    ``RMSNorm`` (+ ``rotary``) + the same entry without the arguments: heads
    of 16 and 128, halves and pairs, query groups, with and without a
    ``keep`` mask, padded lengths, every layout, the norm without a rotary.
    dq is the gradient of the PROJECTION's output: the other side reaches
    it by autodiff through ``rotary`` and ``RMSNorm``; what ``return_q``
    hands back carries none."""
    results, got = _norm_case(case, True)
    ref_results, want = _norm_case(case, False)
    for a, b in zip(results, ref_results):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def _norm_calls(norm, *, rotary=True, interpret=False, return_q=False):
    """The traced gradient and its three ``pallas_call`` equations at the
    sparse cell's widths (16 query heads on 2 K/V heads of 128 under a
    ``keep`` mask, halves), by kernel name."""
    from horovod_tpu.models.transformer import rotary_tables
    from horovod_tpu.ops.pallas_kernels import QNorm, QRotary

    s = 1024
    x = lambda w: jax.ShapeDtypeStruct((1, s, w), jnp.bfloat16)  # noqa: E731
    shapes = (x(16 * 128), x(2 * 128), x(2 * 128),
              jax.ShapeDtypeStruct((1, s, s), jnp.int8),
              jax.ShapeDtypeStruct((128,), jnp.float32))

    def loss(q, k, v, keep, scale):
        kw = dict(q_norm=QNorm(scale, 1e-6)) if norm else {}
        if rotary:
            kw["q_rotary"] = QRotary(
                *rotary_tables(s, 128, theta=1e7), halves=True
            )
        out, _, *q_seen = flash_attention_with_lse(
            q, k, v, causal=True, layout="bsm", n_heads=16, n_kv_heads=2,
            keep=keep, interpret=interpret, return_q=return_q, **kw
        )
        return out.astype(jnp.float32).sum() + sum(
            x.astype(jnp.float32).sum() for x in q_seen
        )

    traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 4)))(*shapes)
    return traced, {
        e.params["name"].replace("_select", ""): e
        for e in _walk(traced.jaxpr) if e.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize("rotary", [True, False], ids=["rotary", "alone"])
def test_norm_runs_in_the_forward_and_dq_and_nowhere_else(rotary):
    """With a scale: the forward takes it as ``[1, d]`` and writes a second
    array shaped like q, the q its scores see, which dK/dV and dQ read in
    q's place (dK/dV is the call it was: same operands, same body); dQ takes
    the scale and the raw q besides and writes the raw q's gradient in q's
    dtype, which leaves the program as it is, and the scale's gradient as
    one float32 ``[1, d]`` row a program.  Without a rotary the forward
    still writes that second array (a call without either writes none)."""
    traced, calls = _norm_calls(True, rotary=rotary)
    _, plain = _norm_calls(False, rotary=rotary)
    assert sorted(calls) == sorted(plain) == [
        "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq", "hvd_flash_fwd"
    ]
    q_shape = (1, 1024, 2048)
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]  # noqa: E731
    extra = lambda name: sorted(  # noqa: E731
        set(shapes(calls[name].invars)) - set(shapes(plain[name].invars))
    )
    assert len(calls["hvd_flash_bwd_dkv"].invars) == len(
        plain["hvd_flash_bwd_dkv"].invars
    )
    assert extra("hvd_flash_fwd") == extra("hvd_flash_bwd_dq") == [(1, 128)]
    fwd, dq = calls["hvd_flash_fwd"], calls["hvd_flash_bwd_dq"]
    # forward: q in, out and the seen q out
    assert shapes(fwd.outvars).count(q_shape) == 2
    assert shapes(plain["hvd_flash_fwd"].outvars).count(q_shape) == (
        2 if rotary else 1
    )
    # dQ reads one more array of q's shape than without: the raw q
    assert shapes(dq.invars).count(q_shape) == 1 + shapes(
        plain["hvd_flash_bwd_dq"].invars
    ).count(q_shape)
    q_seen, raw_q = fwd.outvars[-1], traced.jaxpr.invars[0]
    for name in ("hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"):
        assert q_seen in calls[name].invars, name
    assert raw_q in dq.invars and raw_q in fwd.invars
    assert raw_q not in calls["hvd_flash_bwd_dkv"].invars
    assert str(calls["hvd_flash_bwd_dkv"].params["jaxpr"]) == str(
        plain["hvd_flash_bwd_dkv"].params["jaxpr"]
    )
    dq_out, _, dscale_rows = dq.outvars  # the row statistic between them
    assert dq_out.aval.dtype == jnp.bfloat16
    assert tuple(dq_out.aval.shape) == q_shape
    assert dq_out in traced.jaxpr.outvars  # as it leaves the kernel
    # 16 heads in two groups of 8, two q blocks of 512: a row a program
    assert tuple(dscale_rows.aval.shape) == (1, 2, 2, 1, 128)
    assert dscale_rows.aval.dtype == jnp.float32
    # the statistic: an rsqrt a head in the forward and in dQ, none in dK/dV
    rsqrts = lambda e: sum(  # noqa: E731
        1 for x in _walk(e.params["jaxpr"]) if x.primitive.name == "rsqrt"
    )
    assert rsqrts(fwd) == rsqrts(dq) == 8
    assert rsqrts(calls["hvd_flash_bwd_dkv"]) == 0
    assert not any(rsqrts(e) for e in plain.values())


def test_return_q_hands_back_the_forwards_own_q():
    """``return_q``: the third result is the forward kernel's second array
    (under ``stop_gradient``: no equation of the backward reads a cotangent
    of it), and asking for it changes no kernel; without a norm or a rotary
    it is q itself."""
    traced, calls = _norm_calls(True, return_q=True)
    _, silent = _norm_calls(True, return_q=False)
    for name, call in calls.items():
        assert str(call.params["jaxpr"]) == str(silent[name].params["jaxpr"])
    q, k, v = (jnp.ones((1, 32, 2, 16)) * c for c in (1.0, 0.5, 0.25))
    out, lse, q_seen = flash_attention_with_lse(
        q, k, v, causal=True, return_q=True, block_q=16, block_k=16
    )
    np.testing.assert_array_equal(q_seen, q)
    two = flash_attention_with_lse(q, k, v, causal=True, block_q=16,
                                   block_k=16)
    assert len(two) == 2
    np.testing.assert_array_equal(out, two[0])


def test_a_call_without_a_norm_traces_what_it_traced():
    """``q_norm=None`` (and ``return_q=False``) is the call without the
    arguments, equation for equation, with and without tables and a mask,
    compiled or interpreted (the parent's jaxprs themselves were compared
    when the argument came: CHANGES.md, PR 48)."""
    for interpret in (False, True):
        for rotary in (False, True):
            kw = dict(rotary=rotary, interpret=interpret)

            def traced(**extra):
                from horovod_tpu.models.transformer import rotary_tables
                from horovod_tpu.ops.pallas_kernels import QRotary

                def loss(q, k, v, keep):
                    out = flash_attention(
                        q, k, v, causal=True, layout="bsm", n_heads=4,
                        n_kv_heads=2, keep=keep, interpret=interpret,
                        q_rotary=QRotary(
                            *rotary_tables(256, 128, theta=1e4), halves=True
                        ) if rotary else None, **extra
                    )
                    return out.astype(jnp.float32).sum()

                x = lambda w: jax.ShapeDtypeStruct(  # noqa: E731
                    (1, 256, w), jnp.bfloat16
                )
                return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
                    x(512), x(256), x(256),
                    jax.ShapeDtypeStruct((1, 256, 256), jnp.int8),
                ))

            assert traced() == traced(q_norm=None), kw


def test_norm_q_counter_counts_the_kernels_that_norm():
    from horovod_tpu.obs import registry

    counter = registry.always().counter("flash.calls.norm_q")
    turning = registry.always().counter("flash.calls.rotary_q")

    def counted(norm, rotary):
        before = counter.get(), turning.get()
        _norm_calls(norm, rotary=rotary, interpret=True)
        return counter.get() - before[0], turning.get() - before[1]

    assert counted(False, False) == (0, 0)
    assert counted(False, True) == (0, 2)
    assert counted(True, True) == (2, 2)  # forward, dQ
    assert counted(True, False) == (2, 0)


@pytest.mark.parametrize("bad,match", [
    (dict(scale=jnp.ones((8,))), "head width 16"),
    (dict(scale=jnp.ones((2, 16))), "head width 16"),
    (dict(eps=jnp.float32(1e-6)), "Python float"),
])
def test_a_norm_that_does_not_fit_q_is_refused(bad, match):
    from horovod_tpu.ops.pallas_kernels import QNorm

    q = jnp.zeros((1, 32, 2 * 16))
    with pytest.raises(ValueError, match=match):
        flash_attention(
            q, q, q, causal=True, layout="bsm", n_heads=2, interpret=True,
            q_norm=QNorm(bad.get("scale", jnp.ones((16,))),
                         bad.get("eps", 1e-6)),
        )


# ---------------------------------------------------------------------------
# Δ at the door: the backward's row statistic, ``Δ' = rowsum(g ⊙ out) −
# g_lse``, is made in dQ where a q block arrives and read from dQ's second
# result by dK/dV; ``g_lse`` reaches the gradients through it alone.  So
# every kind of call is held against attention written out in XLA under a
# NON-ZERO ``lse`` cotangent that differs from row to row.
# ---------------------------------------------------------------------------


# name: (entry, query heads, K/V heads, n, r, dv, sq, skv, (block_q,
# block_k), causal, window, q_rotary + q_norm, keep, weight of lse's term).
# ``entry``: a layout of the q, k, v entry, or "latent" (a head's key is
# ``n`` own columns and ``r`` shared ones).  The padded cases leave padded q
# rows in the last q block (40 of 48, 50 of 64), with and without ``g_lse``.
_DELTA_CASES = {
    "bsm-d64-causal": (
        "bsm", 2, 2, 64, 0, 64, 64, 64, (32, 32), True, None, False, False,
        0.3),
    "bsm-d64-full-cross": (
        "bsm", 2, 2, 64, 0, 64, 32, 48, (16, 16), False, None, False, False,
        0.3),
    "bhsd-d16-causal": (
        "bhsd", 2, 2, 16, 0, 16, 48, 48, (16, 16), True, None, False, False,
        0.3),
    "bshd-d128-dv64-full": (
        "bshd", 2, 2, 128, 0, 64, 32, 32, (16, 16), False, None, False, False,
        0.3),
    "latent-128+64-128": (
        "latent", 2, 2, 128, 64, 128, 64, 64, (32, 32), True, None, False,
        False, 0.3),
    "groups-6to2-d16": (
        "bsm", 6, 2, 16, 0, 16, 64, 64, (32, 32), True, None, False, False,
        0.3),
    "window-w20-4to2-d16": (
        "bsm", 4, 2, 16, 0, 16, 96, 96, (32, 32), True, 20, False, False,
        0.3),
    "rotary-norm-4to2-d16": (
        "bsm", 4, 2, 16, 0, 16, 64, 64, (32, 32), True, None, True, False,
        0.3),
    "keep-4to2-d128-rotary-norm": (
        "bsm", 4, 2, 128, 0, 128, 64, 64, (32, 32), True, None, True, True,
        0.3),
    "keep-2to2-d16": (
        "bsm", 2, 2, 16, 0, 16, 64, 64, (32, 32), True, None, False, True,
        0.3),
    "padded-sq40-bsm-d64-lse": (
        "bsm", 2, 2, 64, 0, 64, 40, 40, (16, 16), True, None, False, False,
        0.3),
    "padded-sq40-bsm-d64-no-lse": (
        "bsm", 2, 2, 64, 0, 64, 40, 40, (16, 16), True, None, False, False,
        0.0),
    "padded-sq50-bhsd-full-lse": (
        "bhsd", 2, 2, 16, 0, 16, 50, 56, (32, 16), False, None, False, False,
        0.3),
    "padded-sq50-groups-keep-no-lse": (
        "bsm", 4, 2, 16, 0, 16, 50, 50, (32, 32), True, None, False, True,
        0.0),
}


@pytest.mark.parametrize("case", list(_DELTA_CASES))
def test_flash_grads_under_an_lse_cotangent_match_xla(case):
    """out, ``lse`` and every gradient of one kind of call a case against
    attention written out in XLA (norm and rotary in front of it, K built
    from latent attention's operands), the loss weighting each row's
    ``lse`` by its own random factor."""
    from horovod_tpu.models.transformer import RMSNorm, rotary, rotary_tables
    from horovod_tpu.ops.pallas_kernels import QNorm, QRotary

    (entry, h, h_kv, n, r, dv, sq, skv, block, causal, window, door, masked,
     lse_weight) = _DELTA_CASES[case]
    latent = entry == "latent"
    d = n + r
    keys = jax.random.split(jax.random.PRNGKey(31), 7)
    q = jax.random.normal(keys[0], (1, sq, h, d))
    if latent:
        k = jax.random.normal(keys[1], (1, skv, h, n + dv))  # kv
        v = jax.random.normal(keys[2], (1, skv, r))  # the shared key
    else:
        k = jax.random.normal(keys[1], (1, skv, h_kv, d))
        v = jax.random.normal(keys[2], (1, skv, h_kv, dv))
    w = jax.random.normal(keys[3], (1, sq, h, dv))
    u = lse_weight * jax.random.normal(keys[4], (1, h, sq))
    scale = 1.0 + 0.3 * jax.random.normal(keys[5], (d,))
    keep = None
    if masked:  # the diagonal kept, so every row sees a key
        keep = ((jax.random.uniform(keys[6], (1, skv, sq)) < 0.5)
                | jnp.eye(skv, sq, dtype=bool)).astype(jnp.int8)
    kw = dict(causal=causal, block_q=block[0], block_k=block[1])

    def flash(q, k, v, scale):
        if door:
            kw.update(
                q_norm=QNorm(scale, _NORM_EPS),
                q_rotary=QRotary(*rotary_tables(sq, d, theta=1e4),
                                 halves=True),
            )
        if latent:
            out, lse = flash_attention_latent(
                q.reshape(1, sq, h * d), k.reshape(1, skv, -1), v, n_heads=h,
                **kw,
            )
            return out.reshape(1, sq, h, dv), lse
        out, lse = flash_attention_with_lse(
            *(_in_layout(t, entry) for t in (q, k, v)), layout=entry,
            window=window, keep=keep,
            **(dict(n_heads=h, n_kv_heads=h_kv) if entry == "bsm" else {}),
            **kw,
        )
        return _from_layout(out, entry, dv), lse

    def written_out(q, k, v, scale):
        if door:
            q = rotary(RMSNorm(_NORM_EPS, jnp.float32).apply(
                {"params": {"scale": scale}}, q
            ), theta=1e4, halves=True)
        if latent:
            k, v = _built_keys(k, v, n)
        return _xla_attention(
            q, k, v, causal=causal, window=window, keep=keep
        )

    def grads(fn):
        def loss(*operands):
            out, lse = fn(*operands)
            return jnp.sum(out * w) + jnp.sum(lse * u), (out, lse)
        with jax.default_matmul_precision("highest"):
            return jax.jit(
                jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)
            )(q, k, v, scale)

    got, (out, lse) = grads(flash)
    want, (ref_out, ref_lse) = grads(written_out)
    np.testing.assert_allclose(out, ref_out, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5, rtol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv", "dscale"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4, err_msg=name)


def _dq_kernel_results(q, k, v, out, lse, g, g_lse, *, causal, n_heads,
                       block):
    """What the dQ kernel of a packed call's backward returns, ``(dq, Δ'
    rows)``: the backward traced as the entries build it, cut off behind
    that kernel and evaluated (interpreter)."""
    import functools

    from horovod_tpu.ops import pallas_kernels as pk

    st = pk._Static(
        1.0 / np.sqrt(q.shape[-1] // n_heads), causal, block, block, True,
        n_heads,
    )
    p = st.plan(q, k, v, None)
    operands = (q, k, v, pk._geometry(0, 0, p.skv), out, lse, g, g_lse)
    traced = jax.make_jaxpr(functools.partial(
        pk._flash_bwd_call.__wrapped__, p=p, sm_scale=st.sm_scale,
        causal=causal,
    ))(*operands)
    eqns = traced.jaxpr.eqns
    (at,) = [
        i for i, e in enumerate(eqns) if e.primitive.name == "pallas_call"
        and e.params["name"] == "hvd_flash_bwd_dq"
    ]
    cut = traced.jaxpr.replace(
        eqns=eqns[:at + 1], outvars=list(eqns[at].outvars),
        debug_info=traced.jaxpr.debug_info._replace(result_paths=None),
    )
    return jax.core.eval_jaxpr(
        cut, traced.consts, *jax.tree.leaves(operands)
    )


@pytest.mark.parametrize("sq,g_lse_weight", [(64, 0.0), (64, 1.0), (40, 1.0)])
@pytest.mark.parametrize("causal", [True, False])
def test_dq_writes_the_row_statistic_to_float32_rounding(causal, sq,
                                                         g_lse_weight):
    """dQ's second result is ``einsum(g, out) − g_lse`` with float32
    products and a float32 sum, from bf16 ``g`` and ``out``, on all eight
    sublanes of the statistics' layout; padded q rows (40 of 48) read 0."""
    h, d, block = 2, 64, 16
    keys = jax.random.split(jax.random.PRNGKey(37), 6)
    q, k, v, out, g = (
        jax.random.normal(key, (2, sq, h * d), jnp.bfloat16)
        for key in keys[:5]
    )
    lse = jnp.full((2, h, sq), 3.0)  # any finite value: Δ' does not read it
    g_lse = g_lse_weight * jax.random.normal(keys[5], (2, h, sq))
    dq, rows = _dq_kernel_results(
        q, k, v, out, lse, g, g_lse, causal=causal, n_heads=h, block=block
    )
    sq_pad = -(-sq // block) * block
    assert dq.shape == (2, sq_pad, h * d) and dq.dtype == jnp.bfloat16
    assert rows.shape == (2, h, 8, sq_pad) and rows.dtype == jnp.float32
    want = jnp.einsum(
        "bqhd,bqhd->bhq", g.astype(jnp.float32).reshape(2, sq, h, d),
        out.astype(jnp.float32).reshape(2, sq, h, d), precision="highest",
    ) - g_lse
    for sublane in range(8):
        np.testing.assert_allclose(
            rows[:, :, sublane, :sq], want, rtol=2e-6, atol=2e-6
        )
    assert not np.asarray(rows[..., sq:]).any()


def test_delta_q_counter_counts_a_backward_built():
    """``flash.calls.delta_q``: one a backward built (its dQ kernel makes
    the statistic), none for a forward, whatever the entry."""
    from horovod_tpu.obs import registry

    counter = registry.always().counter("flash.calls.delta_q")
    x = jax.ShapeDtypeStruct((1, 64, 2 * 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 64, 2 * 192), jnp.float32)
    rope = jax.ShapeDtypeStruct((1, 64, 64), jnp.float32)

    def plain(q, k, v):
        return flash_attention(
            q, k, v, causal=True, layout="bsm", n_heads=2
        ).sum()

    def latent(q, kv, rope):
        return flash_attention_latent(q, kv, rope, n_heads=2)[0].sum()

    def counted(fn, *operands):
        before = counter.get()
        jax.eval_shape(fn, *operands)
        return counter.get() - before

    assert counted(plain, x, x, x) == 0
    assert counted(jax.grad(plain, argnums=(0, 1, 2)), x, x, x) == 1
    assert counted(latent, kv, kv, rope) == 0
    assert counted(jax.grad(latent, argnums=(0, 1, 2)), kv, kv, rope) == 1
