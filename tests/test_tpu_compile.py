"""Every public Pallas kernel compiles for the TPU v5e at model widths.

No chip is needed: libtpu compiles for a *described* ``v5e:2x2`` topology
(``jax.experimental.topologies``), with ``interpret=False`` and shapes in
place of arrays, and raises what the chip's compiler would raise. Nothing
runs, so these say nothing about results or times — they catch what the
interpreter cannot (a primitive Mosaic will not legalize, a misaligned
slice, too much VMEM) before a chip call is spent on it.

One file, one process: the TPU compiler's lock file allows one loader at a
time, so no fast-tier test may load it from a child process.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one described v5e chip; the persistent compile cache is
    off meanwhile (an entry compiled for a described chip cannot be read
    back without one, and the next compile would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / cannot describe the chip here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "batch,seq,causal,heads",
    [(16, 1024, True, 12), (16, 1024, True, 6), (16, 1024, True, 16),
     (4, 2048, True, 12), (16, 640, True, 18), (32, 512, False, 12)],
    ids=["gpt2-16x1024-causal", "6-heads-causal", "16-heads-causal",
         "s2048-causal", "18-heads-s640-causal", "bert-32x512"],
)
def test_flash_attention_fwd_bwd_compiles(v5e, batch, seq, causal, heads):
    """Packed ``bsm`` layout with heads of 64, as ``models/transformer.py``
    calls it (no block sizes given); forward and both backward kernels.
    The causal cases cross what the plan decides from the shape: the whole
    K/V of 1024 resident (12 heads: groups of 4), head counts whose
    budget-sized group would be 3 heads, 192 lanes, which Mosaic refuses
    (6, 18), a padded length, and two K/V blocks (s 2048: the forward's
    unmasked slab)."""

    def loss(q, k, v):
        out = pk.flash_attention(
            q, k, v, causal=causal, layout="bsm", n_heads=heads,
            interpret=False,
        )
        return out.astype(jnp.float32).sum()

    qkv = ((batch, seq, 64 * heads), jnp.bfloat16)
    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), v5e, qkv, qkv, qkv)
    assert hlo.count("tpu_custom_call") >= 3  # fwd + dkdv + dq


def test_fused_adamw_compiles(v5e):
    n = 124 * 1024 * 1024  # a GPT-2-small-sized fp32 flat buffer

    def update(p, m, v, g, count):
        return pk.fused_adamw_update_pallas(
            p, m, v, g, count, lr=3e-4, interpret=False
        )

    buf = ((n,), jnp.float32)
    hlo = _compile(update, v5e, buf, buf, buf, buf, ((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_int8_matmul_compiles(v5e):
    def mm(x, w, s):
        return pk.int8_matmul_pallas(x, w, s, interpret=False)

    hlo = _compile(
        mm, v5e,
        ((16384, 768), jnp.bfloat16),
        ((768, 3072), jnp.int8),
        ((3072,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_fp8_matmul_compiles(v5e):
    def mm(x, w, s):
        return pk.fp8_matmul_pallas(x, w, s, interpret=False)

    hlo = _compile(
        mm, v5e,
        ((16384, 768), jnp.float8_e4m3fn),
        ((768, 3072), jnp.float8_e4m3fn),
        ((), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize(
    "wire_dtype,qmax,integer",
    [(jnp.int8, 127.0, True), (jnp.float8_e4m3fn, 448.0, False)],
    ids=["int8", "fp8"],
)
def test_blockwise_quantize_dequantize_compile(v5e, wire_dtype, qmax, integer):
    def quant(rows):
        return pk.quantize_blockwise_pallas(
            rows, qmax=qmax, wire_dtype=wire_dtype, integer=integer,
            interpret=False,
        )

    def dequant(q, s):
        return pk.dequantize_blockwise_pallas(q, s, interpret=False)

    nb, block = 65536, 256
    assert "tpu_custom_call" in _compile(
        quant, v5e, ((nb, block), jnp.float32)
    )
    assert "tpu_custom_call" in _compile(
        dequant, v5e, ((nb, block), wire_dtype), ((nb,), jnp.float32)
    )
