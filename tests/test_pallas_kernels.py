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


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 48, 2, 16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(dot_product_attention(q, k, v, causal=causal)))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


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


def test_transformer_use_flash_matches_dense():
    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel

    kwargs = dict(
        vocab_size=128, max_len=32, d_model=32, n_heads=2, n_layers=1,
        d_ff=64, dtype=jnp.float32,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0, 128)
    # Pin the baseline to the dense path: use_flash=None auto-selects
    # flash on TPU, which would make this comparison flash-vs-flash.
    m1 = GPT2LMModel(GPT2Config(use_flash=False, **kwargs))
    m2 = GPT2LMModel(GPT2Config(use_flash=True, **kwargs))
    params = m1.init(jax.random.PRNGKey(9), tokens)
    np.testing.assert_allclose(
        m1.apply(params, tokens),
        m2.apply(params, tokens),
        atol=1e-5,
        rtol=1e-5,
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
