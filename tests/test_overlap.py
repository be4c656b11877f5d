"""Overlap pipeline: microbatched gradient accumulation, staggered bucket
dispatch, scheduler enablement, and input prefetch.

The contract under test is the ISSUE's acceptance bar: the overlapped /
microbatched step is the plain step within fp tolerance (replicated AND
sharded, donation preserved), accumulation has mean semantics, the
prefetch wrapper neither drops nor reorders, and the enablement layer
degrades to a no-op on CPU test platforms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.obs import registry as obs_registry
from horovod_tpu.ops.fusion import fused_allreduce, pack, unpack
from horovod_tpu.ops.layout import (
    ASYNC_LEAF_BYTES,
    overlap_compiler_options,
    overlap_threshold_bytes,
)
from horovod_tpu.parallel import dp
from horovod_tpu.parallel.dp import accumulate_gradients


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


def _batch(seed=1, n=32):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(n, 4), jnp.float32),
        jnp.asarray(rng.randn(n, 3), jnp.float32),
    )


def _copy(tree):
    return jax.tree.map(jnp.array, tree)


# -- numerical parity ----------------------------------------------------


@pytest.mark.parametrize("sharded", [False, True], ids=["replicated", "sharded"])
def test_overlap_accum_matches_plain_step(world8, sharded):
    """overlap=True + accum_steps=4 walks the same trajectory as the
    plain step (fp tolerance; the accumulation only reorders the batch
    sum), on both optimizer paths, with donation left on (default)."""
    step_p, opt_p = dp.make_train_step(_loss, optax.adamw(1e-2), sharded=sharded)
    step_o, opt_o = dp.make_train_step(
        _loss, optax.adamw(1e-2), sharded=sharded, overlap=True, accum_steps=4
    )
    sp = dp.init_state(_copy(_params()), opt_p)
    so = dp.init_state(_copy(_params()), opt_o)
    for i in range(4):
        batch = _batch(seed=i)
        sp, lp = step_p(sp, batch)
        so, lo = step_o(so, batch)
        np.testing.assert_allclose(float(lp), float(lo), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(sp.params), jax.tree.leaves(so.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        )
    assert int(so.step) == 4


@pytest.mark.parametrize(
    "kwargs",
    [
        {"overlap": True, "stagger": False},  # unchained overlap
        {"stagger": True},  # explicit chaining WITHOUT overlap (honored)
    ],
    ids=["overlap-no-stagger", "stagger-only"],
)
def test_overlap_stagger_kwarg_parity(world8, kwargs):
    """stagger= per-call (docs: every HVDTPU_OVERLAP* knob is also
    settable per-call) is honored — including an explicit stagger=True
    without overlap — and stays exact."""
    step_p, opt_p = dp.make_train_step(_loss, optax.adamw(1e-2))
    step_u, opt_u = dp.make_train_step(_loss, optax.adamw(1e-2), **kwargs)
    sp = dp.init_state(_copy(_params()), opt_p)
    su = dp.init_state(_copy(_params()), opt_u)
    for i in range(2):
        sp, _ = step_p(sp, _batch(seed=i))
        su, _ = step_u(su, _batch(seed=i))
    for a, b in zip(jax.tree.leaves(sp.params), jax.tree.leaves(su.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        )


def test_accum_without_overlap_also_matches(world8):
    """accum_steps alone (no overlap machinery) is equally exact."""
    step_p, opt_p = dp.make_train_step(_loss, optax.adamw(1e-2))
    step_a, opt_a = dp.make_train_step(_loss, optax.adamw(1e-2), accum_steps=2)
    sp = dp.init_state(_copy(_params()), opt_p)
    sa = dp.init_state(_copy(_params()), opt_a)
    for i in range(3):
        sp, _ = step_p(sp, _batch(seed=i))
        sa, _ = step_a(sa, _batch(seed=i))
    for a, b in zip(jax.tree.leaves(sp.params), jax.tree.leaves(sa.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        )


def test_accumulate_gradients_fp32_accumulator_for_bf16():
    """Accumulation runs in fp32 even for bf16 params (K-1 rounded adds
    would drift the mean) and returns grads in the gradient dtype."""
    rng = np.random.RandomState(2)
    params = {"w": jnp.asarray(rng.randn(6, 2), jnp.bfloat16)}
    batch = (
        jnp.asarray(rng.randn(24, 6), jnp.float32),
        jnp.asarray(rng.randn(24, 2), jnp.float32),
    )

    def loss(p, b):
        x, y = b
        return jnp.mean((x @ p["w"].astype(jnp.float32) - y) ** 2)

    _, _, g1 = accumulate_gradients(loss, params, batch, 1)
    _, _, g8 = accumulate_gradients(loss, params, batch, 8)
    assert g8["w"].dtype == g1["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(g8["w"], np.float32),
        np.asarray(g1["w"], np.float32),
        rtol=2e-2,
        atol=1e-3,
    )


def test_accumulate_gradients_mean_semantics():
    """Mean of per-microbatch mean losses/gradients == full-batch mean
    (equal microbatches), checked against jax.value_and_grad directly."""
    params = _params()
    batch = _batch(seed=3, n=24)
    loss_full, grads_full = jax.value_and_grad(_loss)(params, batch)
    for k in (1, 2, 3, 4, 6):
        loss, aux, grads = accumulate_gradients(_loss, params, batch, k)
        assert aux is None
        np.testing.assert_allclose(float(loss), float(loss_full), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_full)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )


def test_accumulate_gradients_has_aux_from_last_microbatch():
    def loss_aux(p, b):
        x, y = b
        return _loss(p, b), jnp.mean(x)

    batch = _batch(seed=5, n=8)
    _, aux, _ = accumulate_gradients(loss_aux, _params(), batch, 4, has_aux=True)
    # Documented semantics: aux comes from the LAST microbatch.
    np.testing.assert_allclose(
        float(aux), float(jnp.mean(batch[0][-2:])), rtol=1e-6
    )


def test_accum_validation_errors():
    with pytest.raises(ValueError, match="accum_steps"):
        accumulate_gradients(_loss, _params(), _batch(), 0)
    with pytest.raises(ValueError, match="not divisible"):
        accumulate_gradients(_loss, _params(), _batch(n=10), 4)


def test_make_train_step_rejects_bad_accum(world8):
    with pytest.raises(ValueError, match="accum_steps"):
        dp.make_train_step(_loss, optax.adamw(1e-2), accum_steps=0)


# -- fusion dispatch order ----------------------------------------------


def test_bucketize_reverse_layer_order_roundtrip():
    """Buckets are packed tail-of-tree first (the grads backward produces
    first), slot indices keep original positions, and unpack round-trips
    exactly."""
    leaves = [jnp.arange(6, dtype=jnp.float32) + i for i in range(5)]
    # 24-byte threshold: one 6-element fp32 leaf per bucket.
    buffers, spec = pack(leaves, threshold_bytes=24)
    assert len(buffers) == 5
    # First bucket holds the LAST leaf.
    first_slots = spec.buckets[0]
    assert [s.index for s in first_slots] == [4]
    np.testing.assert_array_equal(
        np.asarray(buffers[0]), np.asarray(leaves[4])
    )
    out = unpack(buffers, spec)
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stagger_is_numerically_identity(world8):
    rng = np.random.RandomState(2)
    tree = {
        "a": jnp.asarray(rng.randn(16), jnp.float32),
        "b": jnp.asarray(rng.randn(8), jnp.float32),
        "c": jnp.asarray(rng.randn(4), jnp.float32),
    }

    def run(stagger):
        @hvd.spmd(out_specs=hvd.P())
        def f():
            # 64-byte threshold -> several buckets -> a real chain.
            return fused_allreduce(
                tree, op=hvd.Sum, threshold_bytes=64, stagger=stagger
            )

        return f()

    plain, chained = run(False), run(True)
    # The barrier chain changes the compiled schedule (different combiner
    # grouping on CPU), so equality is fp-level, not bitwise.
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(chained)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


# -- the overlapped exchange: the combiner's limit, the state's place ------


@pytest.mark.parametrize("asked, limit", [
    (None, ASYNC_LEAF_BYTES),        # the 64 MB default never survives
    (128 << 20, ASYNC_LEAF_BYTES),
    (ASYNC_LEAF_BYTES, ASYNC_LEAF_BYTES),
    (4096, 4096),                    # a smaller threshold is the caller's
])
def test_overlap_threshold_is_the_fusion_threshold_up_to_the_size_rule(
    asked, limit
):
    assert overlap_threshold_bytes(asked) == limit


@pytest.mark.parametrize("kwargs, n_devices, limit", [
    ({}, 8, ASYNC_LEAF_BYTES),
    ({"threshold_bytes": 4096}, 8, 4096),
    ({}, 1, None),
    ({"sharded": True}, 8, None),
    ({"compression": hvd.Compression.int8}, 8, None),
    ({"overlap": True, "sharded": True}, 8, "fusion threshold"),
])
def test_compile_options_follow_the_mesh_and_the_path(
    monkeypatch, kwargs, n_devices, limit
):
    """What ``make_train_step`` tells the compiler: on the replicated path
    with more than one device on the axis, the overlap options and the
    combiner at the exchange's limit; on one device nothing at all; on the
    sharded and quantized paths only with ``overlap=True``, at the fusion
    threshold."""
    from conftest import cpu_devices

    monkeypatch.setattr(dp, "overlap_compiler_options",
                        lambda platform: {"overlap": "on"})
    monkeypatch.setattr(dp, "collective_compiler_options",
                        lambda t, platform: {"combiner": t})
    seen = {}
    real_jit = jax.jit

    def spy(fn, **kw):
        if "compiler_options" in kw:  # the step's own jit; CPU knows no option
            seen.update(kw)
            kw = {k: v for k, v in kw.items() if k != "compiler_options"}
        return real_jit(fn, **kw)

    monkeypatch.setattr(jax, "jit", spy)
    hvd.init(devices=cpu_devices(n_devices))
    try:
        step, opt = dp.make_train_step(_loss, optax.adamw(1e-2), **kwargs)
        state = dp.init_state(_copy(_params()), opt)
        state, loss = step(state, _batch())
        jax.block_until_ready(loss)
    finally:
        hvd.shutdown()
    if limit is None:
        assert seen.get("compiler_options") is None
    else:
        if limit == "fusion threshold":  # left to layout.py's default
            limit = None
        assert seen["compiler_options"] == {"overlap": "on", "combiner": limit}


def test_state_is_placed_once_only_where_the_axis_spans_devices(monkeypatch):
    """On more than one device the replicated step places a state that is
    not on the mesh before it dispatches it (the one ``init_state`` made,
    never the one it returned), ``step.lower`` lowers for that place, and
    so the cell's flow (lower, compile, call, call) builds one program;
    with one device on the axis the step is built as it always was
    (ROADMAP D1b's second build included)."""
    from conftest import cpu_devices

    def lowerings():
        return hvd.obs.snapshot()["counters"].get(
            "build.lowerings.hvd_train_step", 0
        )

    puts = []
    real_put = jax.device_put

    def counting_put(x, *args, **kwargs):
        puts.append(type(x).__name__)
        return real_put(x, *args, **kwargs)

    seen = {}
    for n in (8, 1):
        hvd.init(devices=cpu_devices(n))
        try:
            step, opt = dp.make_train_step(_loss, optax.adamw(1e-2))
            state = dp.init_state(_copy(_params()), opt)
            n_leaves = len(jax.tree.leaves(state))
            before = lowerings()
            step.lower(state, _batch()).compile()
            monkeypatch.setattr(jax, "device_put", counting_put)
            for i in range(3):
                state, loss = step(state, _batch(seed=i))
            jax.block_until_ready(loss)
            monkeypatch.setattr(jax, "device_put", real_put)
            seen[n] = (lowerings() - before, len(puts) // n_leaves)
            del puts[:]
        finally:
            hvd.shutdown()
    assert seen == {8: (1, 1), 1: (2, 0)}


_TPU_SCHEDULED_HLO = """\
HloModule jit_step, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%fused_start (p: bf16[8,4]) -> (bf16[8,4], f32[8,4], u32[]) {
  %p = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.7 = f32[8,4]{1,0:T(8,128)} all-reduce(%p), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/shard_map/hvd_reduce/psum"}
  ROOT %cc = (bf16[8,4]{1,0}, f32[8,4]{1,0}, u32[]) custom-call(%all-reduce.7), custom_call_target="AsyncCollectiveStart"
}

%fused_done (q: bf16[8,4], r: f32[8,4], f: u32[]) -> f32[8,4] {
  %q = bf16[8,4]{1,0} parameter(0)
  %r = f32[8,4]{1,0} parameter(1)
  %f = u32[] parameter(2)
  %all-reduce.9 = f32[8,4]{1,0} all-reduce(%q), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/shard_map/hvd_reduce/psum"}
  ROOT %cd = f32[8,4]{1,0} custom-call(%q, %r, %all-reduce.9, %f), custom_call_target="AsyncCollectiveDone"
}

%fused_dw (x: bf16[16,8], y: bf16[16,4]) -> f32[8,4] {
  %x = bf16[16,8]{1,0} parameter(0)
  %y = bf16[16,4]{1,0} parameter(1)
  ROOT %conv = f32[8,4]{1,0} convolution(%x, %y), dim_labels=fb_io->bf
}

%fused_adam (g: f32[8,4], w: f32[8,4]) -> f32[8,4] {
  %g = f32[8,4]{1,0} parameter(0)
  %w = f32[8,4]{1,0} parameter(1)
  ROOT %upd = f32[8,4]{1,0} subtract(%w, %g), metadata={op_name="jit(step)/shard_map/hvd_update/sub"}
}

ENTRY %main (g0: bf16[8,4], x: bf16[16,8], y: bf16[16,4], w: f32[8,4], small: f32[3], l: f32[]) -> f32[8,4] {
  %g0 = bf16[8,4]{1,0} parameter(0)
  %x = bf16[16,8]{1,0} parameter(1)
  %y = bf16[16,4]{1,0} parameter(2)
  %w = f32[8,4]{1,0} parameter(3)
  %small = f32[3]{0} parameter(4)
  %l = f32[] parameter(5)
  %async-collective-start.1 = (bf16[8,4]{1,0}, f32[8,4]{1,0}, u32[]) fusion(%g0), kind=kCustom, calls=%fused_start
  %gte.0 = bf16[8,4]{1,0} get-tuple-element(%async-collective-start.1), index=0
  %gte.1 = f32[8,4]{1,0} get-tuple-element(%async-collective-start.1), index=1
  %gte.2 = u32[] get-tuple-element(%async-collective-start.1), index=2
  %fusion.dw = f32[8,4]{1,0} fusion(%x, %y), kind=kOutput, calls=%fused_dw
  %fusion.adam = f32[8,4]{1,0} fusion(%fusion.dw, %w), kind=kLoop, calls=%fused_adam
  %async-collective-done.1 = f32[8,4]{1,0} fusion(%gte.0, %gte.1, %gte.2), kind=kCustom, calls=%fused_done, metadata={op_name="jit(step)/shard_map/hvd_reduce/psum"}
  %all-reduce.3 = (f32[3]{0}, f32[3]{0}) all-reduce(%small, %small), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/shard_map/hvd_reduce/psum"}
  %all-reduce.4 = f32[] all-reduce(%l), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/shard_map/hvd_loss_avg/psum"}
  ROOT %out = f32[8,4]{1,0} add(%async-collective-done.1, %fusion.adam)
}
"""

_PLAIN_ASYNC_HLO = """\
HloModule m, is_scheduled=true

ENTRY %main (g: f32[8,4], x: f32[8,8]) -> f32[8,4] {
  %g = f32[8,4]{1,0} parameter(0)
  %x = f32[8,8]{1,0} parameter(1)
  %all-reduce-start.2 = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-reduce-start(%g), replica_groups={{0,1}}, to_apply=%add
  %dot.5 = f32[8,4]{1,0} dot(%x, %g), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %all-reduce-done.2 = f32[8,4]{1,0} all-reduce-done(%all-reduce-start.2)
  ROOT %out = f32[8,4]{1,0} add(%all-reduce-done.2, %dot.5)
}
"""


def test_collective_schedule_reads_pairs_and_what_lies_between():
    from horovod_tpu.analysis import collective_schedule

    sched = collective_schedule(_TPU_SCHEDULED_HLO)
    assert (sched["n_async"], sched["n_sync"]) == (1, 1)
    (pair,) = sched["async"]
    assert pair["name"] == "async-collective-start.1"
    assert pair["done"] == "async-collective-done.1"
    assert pair["bytes"] == 8 * 4 * 4 and pair["operands"] == 1
    assert pair["index"] < pair["done_index"]
    assert (pair["matmuls_between"], pair["updates_between"]) == (1, 1)
    (sync,) = sched["sync"]  # the loss average is no gradient collective
    assert sync["name"] == "all-reduce.3" and sync["operands"] == 2
    assert sync["bytes"] == 2 * 3 * 4
    assert sched["async_bytes_share"] == pytest.approx(128 / (128 + 24))
    assert collective_schedule(_TPU_SCHEDULED_HLO, scope=None)["n_sync"] == 2


def test_collective_schedule_reads_plain_start_done_pairs():
    from horovod_tpu.analysis import collective_schedule

    sched = collective_schedule(_PLAIN_ASYNC_HLO, scope=None)
    assert (sched["n_async"], sched["n_sync"]) == (1, 0)
    (pair,) = sched["async"]
    assert pair["bytes"] == 8 * 4 * 4  # the result, not operand + result
    assert pair["matmuls_between"] == 1
    assert sched["async_bytes_share"] == 1.0


# -- scheduler enablement ------------------------------------------------


def test_overlap_compiler_options_platforms():
    assert overlap_compiler_options("cpu") == {}
    tpu = overlap_compiler_options("tpu")
    assert tpu["xla_tpu_enable_latency_hiding_scheduler"] == "true"
    gpu = overlap_compiler_options("gpu")
    assert "xla_gpu_enable_latency_hiding_scheduler" in gpu


def test_enable_overlap_scheduler_cpu_noop(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    assert hvd.enable_overlap_scheduler() == ()
    assert "latency_hiding" not in (jax.config.jax_platforms or "") + (
        __import__("os").environ["XLA_FLAGS"]
    )


def test_enable_overlap_scheduler_tpu_with_cpu_fallback(monkeypatch):
    # JAX_PLATFORMS="tpu,cpu" (TPU primary, CPU fallback) must still arm
    # the flags — only a PRIMARY cpu platform is a no-op.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    assert hvd.enable_overlap_scheduler()


def test_enable_overlap_scheduler_token_match(monkeypatch):
    # A user-set sibling flag whose name is a superstring must not
    # suppress the shorter flag (substring-match regression).
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=false",
    )
    added = hvd.enable_overlap_scheduler(platform="tpu")
    assert "--xla_tpu_enable_async_collective_fusion=true" in added
    assert not any("fuse_all_gather" in f for f in added)


def test_enable_overlap_scheduler_gpu_gets_gpu_flags(monkeypatch):
    # A GPU platform must get the xla_gpu_* scheduler flag, never the
    # TPU knobs (unknown xla_tpu_* tokens are fatal on non-TPU builds).
    for plat in ("cuda", "gpu", "cuda,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", plat)
        monkeypatch.setenv("XLA_FLAGS", "")
        added = hvd.enable_overlap_scheduler()
        assert added == ("--xla_gpu_enable_latency_hiding_scheduler=true",), (
            plat, added,
        )
        assert not any("xla_tpu" in f for f in added)


def test_enable_overlap_scheduler_autodetects_gpu(monkeypatch):
    # JAX_PLATFORMS unset on a CUDA host (cuda plugin installed, no
    # libtpu): the empty-platform probe must arm the GPU flag, not ().
    # Prefix-matched (jax_cuda13_plugin here), not a version list.
    import importlib.util as _ilu
    import pkgutil
    import types

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("TPU_NAME", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setattr(
        _ilu, "find_spec", lambda name, *a, **kw: None
    )  # no libtpu
    monkeypatch.setattr(
        pkgutil,
        "iter_modules",
        lambda *a, **kw: [types.SimpleNamespace(name="jax_cuda13_plugin")],
    )
    added = hvd.enable_overlap_scheduler()
    assert added == ("--xla_gpu_enable_latency_hiding_scheduler=true",)


def test_enable_overlap_scheduler_autodetects_tpu(monkeypatch):
    # JAX_PLATFORMS unset and libtpu importable: the empty-platform probe
    # arms the TPU knobs.
    import importlib.util as _ilu

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setattr(
        _ilu, "find_spec", lambda name, *a, **kw: object()
    )  # libtpu "present"
    added = hvd.enable_overlap_scheduler()
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in added


def test_enable_overlap_scheduler_tpu_sets_flags(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "")
    added = hvd.enable_overlap_scheduler(platform="tpu")
    assert added, "explicit platform='tpu' must arm the flags"
    import os

    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in os.environ[
        "XLA_FLAGS"
    ]
    # Idempotent: a second call adds nothing.
    assert hvd.enable_overlap_scheduler(platform="tpu") == ()


def test_env_knob_defaults(monkeypatch):
    from horovod_tpu.utils import env as _env

    for var in ("HVDTPU_OVERLAP", "HVDTPU_OVERLAP_ACCUM_STEPS",
                "HVDTPU_PREFETCH_DEPTH", "HVDTPU_OVERLAP_STAGGER"):
        monkeypatch.delenv(var, raising=False)
    assert _env.overlap_default() is False
    assert _env.overlap_accum_steps() == 1
    assert _env.overlap_stagger() is True
    assert _env.prefetch_depth() == 2
    monkeypatch.setenv("HVDTPU_OVERLAP", "1")
    monkeypatch.setenv("HVDTPU_OVERLAP_ACCUM_STEPS", "4")
    monkeypatch.setenv("HVDTPU_PREFETCH_DEPTH", "3")
    assert _env.overlap_default() is True
    assert _env.overlap_accum_steps() == 4
    assert _env.prefetch_depth() == 3


# -- prefetch ------------------------------------------------------------


def test_prefetch_preserves_order_and_exhausts():
    items = [np.full((2,), i, np.float32) for i in range(7)]
    for depth in (1, 2, 5, 20):
        out = list(hvd.prefetch_to_device(iter(items), depth=depth))
        assert len(out) == 7, depth
        for i, o in enumerate(out):
            np.testing.assert_array_equal(np.asarray(o), items[i])


def test_prefetch_empty_iterator():
    assert list(hvd.prefetch_to_device(iter(()), depth=2)) == []


def test_prefetch_depth_validated_eagerly():
    with pytest.raises(ValueError, match="depth"):
        hvd.prefetch_to_device(iter([1]), depth=0)


def test_prefetch_records_occupancy_gauges():
    obs_registry.enable()
    try:
        list(hvd.prefetch_to_device(iter([np.zeros(1)] * 5), depth=3))
        reg = obs_registry.metrics()
        assert reg.gauge("prefetch.depth").get() == 3
        assert 1 <= reg.gauge("prefetch.occupancy").get() <= 3
        assert reg.counter("prefetch.batches").get() >= 5
    finally:
        obs_registry.disable()


# -- overlap telemetry ---------------------------------------------------


def test_step_gauges_mark_overlap_shape(world8):
    obs_registry.enable()
    try:
        step, opt = dp.make_train_step(
            _loss, optax.adamw(1e-2), overlap=True, accum_steps=2
        )
        state = dp.init_state(_copy(_params()), opt)
        state, _ = step(state, _batch())
        reg = obs_registry.metrics()
        assert reg.gauge("overlap.enabled").get() == 1.0
        assert reg.gauge("overlap.accum_steps").get() == 2.0
    finally:
        obs_registry.disable()


# -- heavier end-to-end (slow tier) --------------------------------------


@pytest.mark.slow
def test_overlap_transformer_parity_slow(world8):
    """Multi-bucket transformer (tiny ViT) through the full overlap
    pipeline: sharded + overlap + accum over several steps stays on the
    plain trajectory. Slow tier: real model, several compiles."""
    from horovod_tpu.models.vit import ViT, ViTConfig

    cfg = ViTConfig.tiny(dtype=jnp.float32)
    model = ViT(cfg)
    n = hvd.size()
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(n * 8, 32, 32, 3), jnp.float32)
    labels = jnp.asarray(
        (np.asarray(images).mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    )
    params0 = model.init(jax.random.PRNGKey(0), images[:2])["params"]

    def loss_fn(p, b):
        x, y = b
        logits = model.apply({"params": p}, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    # Tiny threshold so the step really has several buckets to stagger.
    # SGD+momentum, not adam: adam's per-element normalization amplifies
    # fp-level reassociation noise on near-zero gradients into relative
    # divergence, which would test adam's conditioning, not the pipeline.
    step_p, opt_p = dp.make_train_step(
        loss_fn, optax.sgd(1e-2, momentum=0.9), sharded=True,
        threshold_bytes=1 << 14,
    )
    step_o, opt_o = dp.make_train_step(
        loss_fn, optax.sgd(1e-2, momentum=0.9), sharded=True,
        threshold_bytes=1 << 14, overlap=True, accum_steps=4,
    )
    sp = dp.init_state(_copy(params0), opt_p)
    so = dp.init_state(_copy(params0), opt_o)
    for _ in range(3):
        sp, lp = step_p(sp, (images, labels))
        so, lo = step_o(so, (images, labels))
        np.testing.assert_allclose(float(lp), float(lo), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(sp.params), jax.tree.leaves(so.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )
