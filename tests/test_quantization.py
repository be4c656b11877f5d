"""Quantized collectives: blockwise int8/fp8 wire format, error
feedback, residual state (checkpoint/reshard), Pallas kernel parity,
and the fp16 prescale regression.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops import quantization as qz
from horovod_tpu.ops.compression import Compression, is_quantized
from horovod_tpu.ops.fusion import (
    EFResiduals,
    fused_allreduce,
    quantized_bucket_layout,
    quantized_fused_allreduce,
)
from horovod_tpu.parallel import dp
from jax.sharding import PartitionSpec as P


def cpu_devices(n):
    devs = jax.devices("cpu")
    assert len(devs) >= n
    return devs[:n]


def _copy(tree):
    return jax.tree.map(jnp.array, tree)


# -- wire format ---------------------------------------------------------


def test_blockwise_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1000).astype(np.float32) * 3)
    q, s = qz.quantize_blockwise(x, 256, qz.INT8)
    assert q.dtype == jnp.int8 and q.shape == (1000,)
    assert s.shape == (4,) and s.dtype == jnp.float32
    xd = qz.dequantize_blockwise(q, s, 256)
    # Round-to-nearest: per-element error <= scale/2, per block.
    xr = np.asarray(x)
    for b in range(4):
        blk = xr[b * 256:(b + 1) * 256]
        bound = np.abs(blk).max() / 127.0 / 2 * 1.001
        err = np.abs(np.asarray(xd)[b * 256:(b + 1) * 256] - blk)
        assert err.max() <= bound


def test_blockwise_zero_block_and_ragged_tail():
    x = jnp.concatenate(
        [jnp.zeros((16,), jnp.float32), jnp.full((5,), 2.0, jnp.float32)]
    )
    q, s = qz.quantize_blockwise(x, 16, qz.INT8)
    assert q.shape == (21,) and s.shape == (2,)
    xd = qz.dequantize_blockwise(q, s, 16)
    np.testing.assert_allclose(np.asarray(xd), np.asarray(x), atol=1e-2)
    # all-zero block must not divide by zero and must stay exactly zero
    assert not np.any(np.asarray(xd[:16]))


def test_fp8_roundtrip():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(512).astype(np.float32) * 50)
    q, s = qz.quantize_blockwise(x, 128, qz.FP8)
    assert q.dtype == jnp.float8_e4m3fn
    xd = qz.dequantize_blockwise(q, s, 128)
    # e4m3 has a 3-bit mantissa: ~6% worst-case relative rounding.
    np.testing.assert_allclose(
        np.asarray(xd), np.asarray(x),
        atol=float(np.abs(np.asarray(x)).max()) * 0.07,
    )


def test_pallas_interpret_matches_jax():
    """CPU-interpreter parity: the Pallas TPU kernels and the pure-jax
    fallback are the same function (fast tier, no TPU needed)."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4096).astype(np.float32) * 7)
    qj, sj = qz.quantize_blockwise(x, 256, qz.INT8, impl="jax")
    qp, sp = qz.quantize_blockwise(x, 256, qz.INT8, impl="pallas")
    np.testing.assert_array_equal(np.asarray(qj), np.asarray(qp))
    np.testing.assert_array_equal(np.asarray(sj), np.asarray(sp))
    dj = qz.dequantize_blockwise(qj, sj, 256, impl="jax")
    dp_ = qz.dequantize_blockwise(qj, sj, 256, impl="pallas")
    np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp_))


def test_quant_compressor_local_roundtrip():
    comp = Compression.int8.with_block(32)
    assert is_quantized(comp) and not is_quantized(Compression.bf16)
    x = jnp.asarray(np.random.RandomState(3).randn(8, 6), jnp.float32)
    wire, ctx = comp.compress(x)
    assert wire.dtype == jnp.int8
    out = comp.decompress(wire, ctx)
    assert out.shape == x.shape and out.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x), atol=0.05
    )


def test_quantized_wire_bytes_accounting():
    # 1 byte/element + fp32 scale per block: the ~2x-below-bf16 claim.
    assert qz.quantized_wire_bytes(256, 256, qz.INT8) == 256 + 4
    assert qz.quantized_wire_bytes(300, 256, qz.INT8) == 300 + 8
    ratio = qz.quantized_wire_bytes(1 << 20, 256, qz.INT8) / (2 * (1 << 20))
    assert ratio <= 0.55


# -- quantized collectives ----------------------------------------------


def _grads_tree(g):
    g = g.reshape(50)
    return {"w": g[:30].reshape(5, 6), "b": g[30:]}


def test_quantized_allreduce_close_to_mean(world8):
    rng = np.random.RandomState(1)
    g_global = jnp.asarray(rng.randn(8, 50).astype(np.float32))
    wa = hvd.WORLD_AXIS

    @hvd.spmd(in_specs=(P(wa),), out_specs=P())
    def mean_quant(g):
        out, res = quantized_fused_allreduce(
            _grads_tree(g), None,
            compression=Compression.int8.with_block(16),
        )
        assert res is None  # no residuals passed -> none returned
        return jnp.concatenate([out["w"].reshape(-1), out["b"]])

    out = np.asarray(mean_quant(g_global))
    want = np.asarray(g_global).mean(0).reshape(50)
    want = np.concatenate([want[:30], want[30:]])
    assert np.abs(out - want).max() < 0.05


def test_fused_allreduce_delegates_quantized(world8):
    rng = np.random.RandomState(2)
    g_global = jnp.asarray(rng.randn(8, 50).astype(np.float32))
    wa = hvd.WORLD_AXIS

    @hvd.spmd(in_specs=(P(wa),), out_specs=P())
    def f(g):
        out = fused_allreduce(
            _grads_tree(g), op=hvd.Sum,
            compression=Compression.int8.with_block(16),
        )
        return jnp.concatenate([out["w"].reshape(-1), out["b"]])

    out = np.asarray(f(g_global))
    want = np.asarray(g_global).sum(0)
    assert np.abs(out - want).max() < 0.4  # sum: 8x the mean's scale


def test_quantized_bucket_layout_prediction(world8):
    params = {"w": jnp.zeros((100,), jnp.float32)}
    comp = Compression.int8.with_block(16)
    (row,) = quantized_bucket_layout(params, world=8, compression=comp)
    # 100 -> padded to world*block = 128
    assert row["elements"] == 128
    assert row["payload_bytes"] == 128
    assert row["scale_bytes"] == (128 // 16) * 4
    assert row["wire_bytes"] == 128 + 32


# -- error feedback through the train step -------------------------------


def _params():
    rng = np.random.RandomState(0)
    return {
        "w": jnp.asarray(rng.randn(4, 3), jnp.float32),
        "b": jnp.zeros((3,), jnp.float32),
        "c": jnp.asarray(rng.randn(7), jnp.float32),
    }


def _loss(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2) + 0.1 * jnp.sum(params["c"] ** 2)


def _batch(seed=1, n=16):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(n, 4), jnp.float32),
        jnp.asarray(rng.randn(n, 3), jnp.float32),
    )


@pytest.mark.parametrize("sharded", [False, True], ids=["replicated", "zero1"])
def test_quant_step_trains_and_carries_residuals(world8, sharded):
    comp = Compression.int8.with_block(8)
    step, opt = dp.make_train_step(
        _loss, optax.adamw(1e-2), sharded=sharded, compression=comp
    )
    st = dp.init_state(_copy(_params()), opt)
    res = st.opt_state.residual
    assert isinstance(res, EFResiduals)
    # 22 payload elements -> padded to world*block = 64; global view is
    # every rank's residual: [8 * 64].
    assert [int(b.shape[0]) for b in res.buffers] == [512]
    assert res.block == 8
    assert step.lint(st, _batch()) == ()
    losses = []
    for i in range(4):
        st, loss = step(st, _batch(seed=i))
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    norm = float(
        jnp.sqrt(sum(jnp.sum(b**2) for b in st.opt_state.residual.buffers))
    )
    assert norm > 0  # quantization error was captured, not dropped


def test_quant_matches_fp32_trajectory_short(world8):
    step_f, opt_f = dp.make_train_step(_loss, optax.adamw(1e-2))
    step_q, opt_q = dp.make_train_step(
        _loss, optax.adamw(1e-2),
        compression=Compression.int8.with_block(8),
    )
    sf = dp.init_state(_copy(_params()), opt_f)
    sq = dp.init_state(_copy(_params()), opt_q)
    for i in range(5):
        sf, lf = step_f(sf, _batch(seed=i))
        sq, lq = step_q(sq, _batch(seed=i))
    assert abs(float(lf) - float(lq)) / abs(float(lf)) < 0.05


def test_error_feedback_is_load_bearing(world8):
    """The headline convergence evidence: over ~200 steps on an mlp with
    scale-disparate gradients sharing one quantization block,
    quantized+EF lands within 1% of the fp32 final loss while plain int8
    (no EF) is measurably worse — the per-step rounding of the small
    gradient components is bias, and only the residual feedback removes
    it."""
    rng = np.random.RandomState(0)
    w1, h, c, aux = 32, 64, 10, 32
    params = {
        "w1": jnp.asarray(rng.randn(w1, h) * 0.3, jnp.float32),
        "b1": jnp.zeros((h,), jnp.float32),
        "w2": jnp.asarray(rng.randn(h, c) * 0.3, jnp.float32),
        "b2": jnp.zeros((c,), jnp.float32),
        "c": jnp.zeros((aux,), jnp.float32),
    }

    def loss_fn(p, b):
        x, y = b
        hid = jax.nn.relu(x @ p["w1"] + p["b1"])
        logits = hid @ p["w2"] + p["b2"]
        main = optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()
        # The gradient of `c` is ~1e-3 of the main gradients: with ONE
        # scale across the whole bucket it rounds to zero every step
        # unless the error feeds back.
        return main + 1e-3 * jnp.sum((p["c"] - 1.0) ** 2)

    n = 512
    X = rng.randn(n, w1).astype(np.float32)
    Y = rng.randint(0, c, size=(n,)).astype(np.int32)

    def batch(i, bs=64):
        idx = (np.arange(bs) + i * bs) % n
        return jnp.asarray(X[idx]), jnp.asarray(Y[idx])

    def run(compression, ef=True, steps=200):
        step, opt = dp.make_train_step(
            loss_fn, optax.sgd(0.2, momentum=0.9),
            compression=compression, error_feedback=ef,
        )
        st = dp.init_state(_copy(params), opt)
        for i in range(steps):
            st, loss = step(st, batch(i))
        return float(loss)

    coarse = Compression.int8.with_block(1 << 16)  # one scale per bucket
    final_fp32 = run(Compression.none)
    final_ef = run(coarse, ef=True)
    final_noef = run(coarse, ef=False)
    rel_ef = abs(final_ef - final_fp32) / final_fp32
    rel_noef = abs(final_noef - final_fp32) / final_fp32
    assert rel_ef < 0.01, (final_fp32, final_ef)
    assert rel_noef > 0.02, (final_fp32, final_noef)
    assert rel_noef > 2.5 * rel_ef


def test_no_error_feedback_drops_residual_state(world8):
    step, opt = dp.make_train_step(
        _loss, optax.adamw(1e-2),
        compression=Compression.int8.with_block(8), error_feedback=False,
    )
    st = dp.init_state(_copy(_params()), opt)
    assert st.opt_state.residual is None
    st, loss = step(st, _batch())
    assert np.isfinite(float(loss))


# -- residual checkpoint / reshard ---------------------------------------


@pytest.mark.parametrize("sharded", [False, True], ids=["replicated", "zero1"])
def test_residuals_roundtrip_checkpoint_and_reshard(tmp_path, sharded):
    """Save at world 8, restore at world 4: the EF residuals come back
    in the new world's layout with the mean-equivalent value on every
    rank (their effect on the Average-reduced gradient is preserved
    exactly), and training continues."""
    comp = Compression.int8.with_block(8)
    ckdir = str(tmp_path / "ck")
    batch = _batch()

    hvd.init(devices=cpu_devices(8))
    try:
        step8, opt8 = dp.make_train_step(
            _loss, optax.adamw(1e-2), sharded=sharded, compression=comp
        )
        s8 = dp.init_state(_copy(_params()), opt8)
        for i in range(3):
            s8, _ = step8(s8, _batch(seed=i))
        res8 = [np.asarray(b) for b in s8.opt_state.residual.buffers]
        mean8 = [r.reshape(8, -1).sum(0) / 8 for r in res8]
        assert any(np.abs(m).max() > 0 for m in mean8)
        hvd.save_checkpoint(ckdir, s8, step=3)
    finally:
        hvd.shutdown()

    hvd.init(devices=cpu_devices(4))
    try:
        step4, opt4 = dp.make_train_step(
            _loss, optax.adamw(1e-2), sharded=sharded, compression=comp
        )
        target = dp.init_state(_copy(_params()), opt4)
        restored = hvd.restore_checkpoint(ckdir, target)
        res4 = restored.opt_state.residual
        assert isinstance(res4, EFResiduals) and res4.block == 8
        for b4, m8 in zip(res4.buffers, mean8):
            per_rank = np.asarray(b4).reshape(4, -1)
            # every new rank carries the mean-equivalent payload
            for k in range(4):
                np.testing.assert_allclose(
                    per_rank[k][:22], m8[:22], rtol=1e-6
                )
        assert int(restored.step) == 3
        s4, loss = step4(restored, batch)
        assert np.isfinite(float(loss))
    finally:
        hvd.shutdown()


def test_ef_off_sharded_quant_checkpoints(tmp_path, world8):
    """Regression: a quantized ZeRO-1 state WITHOUT error feedback still
    pads buckets to world*block — the recorded ``block`` leaf (not the
    absent residuals) must drive the canonical transforms."""
    comp = Compression.int8.with_block(8)
    step, opt = dp.make_train_step(
        _loss, optax.adamw(1e-2), sharded=True, compression=comp,
        error_feedback=False,
    )
    st = dp.init_state(_copy(_params()), opt)
    st, _ = step(st, _batch())
    assert st.opt_state.residual is None
    assert int(st.opt_state.block) == 8
    d = str(tmp_path / "ck")
    hvd.save_checkpoint(d, st, step=1)  # canonicalize must not raise
    target = dp.init_state(_copy(_params()), opt)
    restored = hvd.restore_checkpoint(d, target)
    assert int(restored.opt_state.block) == 8
    st2, loss = step(restored, _batch())
    assert np.isfinite(float(loss))


def test_explicit_compression_none_beats_quant_env(world8, monkeypatch):
    """Regression: compression=Compression.none passed explicitly must
    opt OUT of HVDTPU_QUANT (bench_quant's baseline leg relies on it)."""
    monkeypatch.setenv("HVDTPU_QUANT", "int8")
    step, opt = dp.make_train_step(
        _loss, optax.adamw(1e-2), compression=Compression.none
    )
    st = dp.init_state(_copy(_params()), opt)
    assert st.opt_state.residual is None


def test_elastic_snapshot_restores_residuals(world8):
    """elastic TrainState snapshots canonicalize EF residuals and the
    restore repacks them for the (possibly resized) world."""
    from horovod_tpu.elastic.state import TrainState as ElasticState

    comp = Compression.int8.with_block(8)
    step, opt = dp.make_train_step(
        _loss, optax.adamw(1e-2), compression=comp
    )
    st = dp.init_state(_copy(_params()), opt)
    st, _ = step(st, _batch())
    es = ElasticState(params=st.params, opt_state=st.opt_state)
    es.save()
    es.opt_state = None
    es.restore()
    res = es.opt_state.residual
    assert isinstance(res, EFResiduals)
    assert [int(np.asarray(b).shape[0]) for b in res.buffers] == [512]


# -- fp16 prescale regression (the legacy cast overflow) ------------------


def test_fp16_compress_prescales_large_values():
    x = jnp.asarray([1e5, -2e5, 3.0], jnp.float32)
    wire, ctx = Compression.fp16.compress(x)
    assert wire.dtype == jnp.float16
    assert np.isfinite(np.asarray(wire, np.float32)).all()
    out = Compression.fp16.decompress(wire, ctx)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x), rtol=2e-3
    )


def test_fp16_compress_identity_for_ordinary_values():
    # scale stays exactly 1 for in-range values: bit-identical to the
    # legacy cast, no behavior change for every ordinary gradient.
    x = jnp.asarray([0.5, -3.25, 100.0], jnp.float32)
    wire, ctx = Compression.fp16.compress(x)
    np.testing.assert_array_equal(
        np.asarray(wire), np.asarray(x.astype(jnp.float16))
    )
    _, scale = ctx
    assert float(scale) == 1.0


def test_fused_allreduce_fp16_large_grads_survive(world8):
    """Regression: the legacy bare cast overflowed any gradient element
    above 65504 to inf ON THE WIRE, poisoning the reduction. The uniform
    (pmax'd) prescale keeps the sum finite and undoes itself."""
    wa = hvd.WORLD_AXIS
    big = jnp.full((8, 50), 1e5, jnp.float32)

    @hvd.spmd(in_specs=(P(wa),), out_specs=P())
    def f(g):
        out = fused_allreduce(
            {"a": g.reshape(50)}, compression=Compression.fp16
        )
        return out["a"]

    out = np.asarray(f(big))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, 1e5, rtol=5e-3)


# -- env knobs and surfacing ---------------------------------------------


def test_quant_env_knobs(monkeypatch):
    from horovod_tpu.utils import env as _env

    monkeypatch.setenv("HVDTPU_QUANT", "int8")
    assert _env.quant_mode() == "int8"
    monkeypatch.setenv("HVDTPU_QUANT", "off")
    assert _env.quant_mode() == ""
    monkeypatch.setenv("HVDTPU_QUANT", "int4")
    with pytest.raises(ValueError, match="int4"):
        _env.quant_mode()
    monkeypatch.setenv("HVDTPU_QUANT_BLOCK", "128")
    assert _env.quant_block() == 128
    monkeypatch.setenv("HVDTPU_QUANT_BLOCK", "0")
    with pytest.raises(ValueError):
        _env.quant_block()


def test_hvdtpu_quant_env_arms_make_train_step(world8, monkeypatch):
    monkeypatch.setenv("HVDTPU_QUANT", "int8")
    monkeypatch.setenv("HVDTPU_QUANT_BLOCK", "8")
    step, opt = dp.make_train_step(_loss, optax.adamw(1e-2))
    st = dp.init_state(_copy(_params()), opt)
    assert isinstance(st.opt_state.residual, EFResiduals)
    assert st.opt_state.residual.block == 8
    st, loss = step(st, _batch())
    assert np.isfinite(float(loss))


def test_quant_gauges_exported(world8, monkeypatch):
    import horovod_tpu.obs as obs

    obs.enable()
    try:
        step, opt = dp.make_train_step(
            _loss, optax.adamw(1e-2),
            compression=Compression.int8.with_block(8),
        )
        st = dp.init_state(_copy(_params()), opt)
        st, _ = step(st, _batch())
        snap = obs.metrics().snapshot()
        gauges = snap["gauges"]
        assert gauges["fusion.quant.allreduce.wire_bytes_per_step"] > 0
        assert gauges["fusion.quant.allreduce.buckets"] == 1
        assert gauges["quant.residual_norm"] >= 0
        assert snap["histograms"]["fusion.quant_ms"]["count"] >= 1
    finally:
        obs.disable()


def test_quant_sweep_variant_lints_clean(world8):
    from horovod_tpu.analysis import harness

    findings = harness.lint_model("mlp", quant="int8")
    assert findings == ()
    # and the broken case still fires: quant prediction vs an
    # unquantized build must produce fusion-parity findings.
    from horovod_tpu.analysis import lint_traced

    step, opt = dp.make_train_step(_loss, optax.adamw(1e-2), lint=False)
    state = jax.eval_shape(lambda: dp.init_state(_params(), opt))
    findings = lint_traced(
        step._mapped_for(state),
        (state, _batch()),
        params=state.params,
        world=8,
        quant=Compression.int8.with_block(8),
    )
    assert any(f.rule == "fusion-parity" for f in findings)


# -- slow tier ------------------------------------------------------------


@pytest.mark.slow
def test_chaos_crash_restore_preserves_ef_state():
    """Convergence soak through the chaos machinery: int8+EF training is
    crashed mid-run; the respawn must restore the full TrainState
    (including residuals) and land on BIT-IDENTICAL final params vs the
    fault-free quantized baseline."""
    from tools import chaos_soak

    res = chaos_soak.run_scenario("quant", steps=5, timeout=240)
    problems = chaos_soak.check_invariants(res, steps=5)
    assert not problems, problems


@pytest.mark.slow
def test_comm_audit_static_quant_gpt2():
    """The wire-reduction acceptance number, in-process: gpt2's
    quantized step must move <= 0.55x the bf16 baseline's ring-wire
    bytes and lint clean."""
    from tools import comm_audit

    base = comm_audit.lint_audit(
        "gpt2_small_16x1024", compression="bf16"
    )
    q = comm_audit.lint_audit(
        "gpt2_small_16x1024", compression="int8"
    )
    assert q["clean"], q["findings"]
    ratio = q["jaxpr_ring_wire_bytes"] / base["jaxpr_ring_wire_bytes"]
    assert ratio <= 0.55, ratio


# -- int8 serving weights (quantize once, scales applied in-kernel) -------


def test_quantize_weight_per_column_bound():
    """Per-output-channel scales: each column's rounding error is
    bounded by that column's own max-abs (the blockwise codec with
    block = K on the column-major view)."""
    from horovod_tpu.ops.quantization import (
        dequantize_weight, quantize_weight,
    )

    rng = np.random.RandomState(5)
    w = jnp.asarray(rng.randn(300, 70), jnp.float32)  # ragged K and N
    qw = quantize_weight(w)
    assert qw.q.dtype == jnp.int8 and qw.q.shape == (300, 70)
    assert qw.scales.shape == (70,)
    err = np.abs(np.asarray(dequantize_weight(qw)) - np.asarray(w))
    col_bound = np.abs(np.asarray(w)).max(0) / 127.0 / 2 * 1.001
    assert (err.max(0) <= col_bound).all()


def test_int8_matmul_pallas_interpret_matches_jax():
    """CPU-interpreter parity for the int8 matmul kernel: identical
    blocked fp32 accumulation order in both impls, so the comparison is
    bit-exact under jit (same contract as the quantize kernels)."""
    from horovod_tpu.ops.quantization import (
        int8_weight_matmul, quantize_weight,
    )

    rng = np.random.RandomState(6)
    for m, k, n in ((5, 300, 70), (16, 512, 128), (1, 64, 10)):
        w = jnp.asarray(rng.randn(k, n), jnp.float32)
        x = jnp.asarray(rng.randn(m, k), jnp.float32)
        qw = quantize_weight(w)
        yj = jax.jit(
            lambda x, qw=qw: int8_weight_matmul(x, qw, impl="jax")
        )(x)
        yp = jax.jit(
            lambda x, qw=qw: int8_weight_matmul(x, qw, impl="pallas")
        )(x)
        np.testing.assert_array_equal(np.asarray(yj), np.asarray(yp))
        # And both track the dequantized reference matmul.
        ref = np.asarray(x) @ (
            np.asarray(qw.q, np.float32) * np.asarray(qw.scales)
        )
        np.testing.assert_allclose(np.asarray(yj), ref, atol=1e-3)


def test_qmatmul_transparent_and_batched():
    from horovod_tpu.ops.quantization import qmatmul, quantize_weight

    rng = np.random.RandomState(7)
    w = jnp.asarray(rng.randn(64, 32), jnp.float32)
    x = jnp.asarray(rng.randn(3, 5, 64), jnp.float32)  # leading batch dims
    plain = np.asarray(qmatmul(x, w))
    np.testing.assert_allclose(plain, np.asarray(x @ w), rtol=1e-6)
    q = np.asarray(qmatmul(x, quantize_weight(w)))
    assert q.shape == plain.shape
    assert np.abs(q - plain).max() < 0.3


def test_quantize_params_picks_big_matmul_weights_only():
    from horovod_tpu.ops.quantization import QuantizedWeight, quantize_params

    rng = np.random.RandomState(8)
    tree = {
        "big": jnp.asarray(rng.randn(128, 64), jnp.float32),  # 8192 elems
        "small": jnp.asarray(rng.randn(8, 8), jnp.float32),
        "bias": jnp.zeros((128,), jnp.float32),
        "ints": jnp.zeros((128, 64), jnp.int32),
    }
    out = quantize_params(tree)
    assert isinstance(out["big"], QuantizedWeight)
    assert not isinstance(out["small"], QuantizedWeight)
    assert not isinstance(out["bias"], QuantizedWeight)
    assert out["ints"].dtype == jnp.int32


def test_quantized_weight_is_a_pytree():
    from horovod_tpu.ops.quantization import quantize_weight

    qw = quantize_weight(jnp.ones((16, 8), jnp.float32))
    leaves, treedef = jax.tree.flatten(qw)
    assert len(leaves) == 2
    back = jax.tree.unflatten(treedef, leaves)
    assert back.dtype_name == qw.dtype_name
    # flows through jit unchanged
    out = jax.jit(lambda w: w.q.sum() + w.scales.sum())(qw)
    assert np.isfinite(float(out))
