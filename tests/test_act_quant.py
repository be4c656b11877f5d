"""int8 activation storage (``HVDTPU_ACT_QUANT``): boundary mechanics,
saved-residual verification, training through the act-quant step, the
memory planner's predicted saving on an activation-dominated build, the
predicted-vs-measured drift gate, and the ``act-quant-unconsumed`` lint
rule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import analysis
from horovod_tpu.analysis import memory as amem
from horovod_tpu.models.mlp import MLP
from horovod_tpu.ops import actquant as aq
from horovod_tpu.parallel import dp


# -- boundary mechanics ---------------------------------------------------


def test_boundary_identity_when_off():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8), jnp.float32)
    assert aq.active_mode() == ""
    assert aq.boundary(x) is x  # zero cost, zero numerics change


def test_boundary_rounds_within_int8_block_bound():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(16, 32), jnp.float32)
    with aq.activate("int8"):
        y = aq.boundary(x)
    assert y.dtype == x.dtype
    err = np.abs(np.asarray(y) - np.asarray(x)).max()
    # Blockwise symmetric int8: error bounded by half a quantization
    # step of the largest block amax.
    assert 0 < err < np.abs(np.asarray(x)).max() / 127.0
    # Non-float inputs pass through untouched.
    ids = jnp.arange(5)
    with aq.activate("int8"):
        assert aq.boundary(ids) is ids


def test_boundary_preserves_bf16_dtype():
    x = jnp.asarray(np.random.RandomState(2).randn(8, 16), jnp.bfloat16)
    with aq.activate("int8"):
        y = aq.boundary(x)
    assert y.dtype == jnp.bfloat16


def test_ste_gradient_is_straight_through():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(64), jnp.float32)

    def f(x):
        with aq.activate("int8"):
            return jnp.sum(aq.boundary(x) ** 2)

    g = jax.grad(f)(x)
    # d/dx sum(deq(x)^2) under STE = 2 * deq(x): the tangent is the
    # identity on x, the value path reads the rounded activation.
    with aq.activate("int8"):
        deq = aq.boundary(x)
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(deq),
                               rtol=1e-5)


def test_resolve_mode():
    assert aq.resolve_mode("") == ""
    assert aq.resolve_mode("int8") == "int8"
    with pytest.raises(ValueError):
        aq.resolve_mode("int4")


# -- saved residuals ------------------------------------------------------


def _mlp_setup(features=(32, 32), batch=16, dim=16, seed=0):
    model = MLP(features=features, num_classes=4)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(batch, dim), jnp.float32)
    y = jnp.asarray(rng.randint(0, 4, size=(batch,)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x[:2])["params"]

    def loss_fn(p, b):
        xs, ys = b
        logits = model.apply({"params": p}, xs)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, ys
        ).mean()

    return params, (x, y), loss_fn


def test_saved_residuals_are_int8_payload_plus_scales():
    """The load-bearing mechanics claim: under the act-quant checkpoint
    policy the backward keeps the named int8 payload + fp32 scales and
    drops the full-precision boundary activations."""
    saved_residuals = pytest.importorskip(
        "jax._src.ad_checkpoint"
    ).saved_residuals
    params, batch, loss_fn = _mlp_setup()

    def armed(p, b):
        with aq.activate("int8"):
            return loss_fn(p, b)

    wrapped = aq.checkpoint_fn(armed, "", "int8")
    res = saved_residuals(wrapped, params, batch)
    saved = [
        (aval, src) for aval, src in res if "argument" not in src
    ]
    dtypes = {str(aval.dtype) for aval, _ in saved}
    assert "int8" in dtypes  # the named payload is stored
    # No full-precision boundary activation survives: every saved f32
    # buffer is a scale vector (1-D), never a [batch, features] tensor.
    f32_shapes = [
        aval.shape for aval, _ in saved if str(aval.dtype) == "float32"
    ]
    assert all(len(s) <= 1 for s in f32_shapes), f32_shapes


def test_act_quant_step_trains(world8):
    params, batch, loss_fn = _mlp_setup()
    step, opt = dp.make_train_step(
        loss_fn, optax.adamw(1e-2), act_quant="int8"
    )
    state = dp.init_state(jax.tree.map(jnp.array, params), opt)
    losses = []
    for _ in range(6):
        state, loss = step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_act_quant_gradients_track_plain(world8):
    # 1024 samples, not 16: int8 rounding flips a few ReLUs, and over 16
    # samples one flip moves a leaf's gradient by several percent, so the
    # error depended on the parameter draw (1-11% across seeds; the
    # installed jax draws other parameters from PRNGKey(0) than the one
    # this bound was set on). At 1024 it is 1-3% for every seed tried.
    params, batch, loss_fn = _mlp_setup(batch=1024)

    def armed(p, b):
        with aq.activate("int8"):
            return loss_fn(p, b)

    g_plain = jax.grad(loss_fn)(params, batch)
    g_q = jax.grad(aq.checkpoint_fn(armed, "", "int8"))(params, batch)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_q)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(b - a) <= 0.05 * np.linalg.norm(a) + 1e-6


# -- memory planner -------------------------------------------------------


def test_memplan_act_quant_reduces_peak_and_matches_measured(world8):
    """On an activation-dominated tower the planner must price the int8
    residuals below the full-precision ones, and the prediction must
    survive the drift gate against a real step's measurement."""
    params, batch, loss_fn = _mlp_setup(
        features=(256,) * 8, batch=4096, dim=256
    )

    def build(act_quant):
        step, opt = dp.make_train_step(
            loss_fn, optax.adamw(1e-4), lint=False, act_quant=act_quant
        )
        state = dp.init_state(jax.tree.map(jnp.array, params), opt)
        return step, state

    step_off, state_off = build("")
    step_on, state_on = build("int8")
    plan_off = step_off.memplan(state_off, batch)
    plan_on = step_on.memplan(state_on, batch)
    # int8 storage moves the planned peak, not just a breakdown row.
    assert plan_on.peak_bytes < plan_off.peak_bytes
    # The saving is in the right ballpark: boundary residuals shrink
    # ~4x, so the whole-step peak must drop by >5% on this build.
    assert plan_on.peak_bytes < 0.95 * plan_off.peak_bytes
    # Predicted-vs-measured drift gate on the quantized build (CPU
    # hosts measure post-step resident bytes against the plan's
    # global_state_bytes; TPU/GPU would gate the device peak).
    before = amem.snapshot_live_ids()
    out = step_on(state_on, batch)
    jax.block_until_ready(out)
    measured = amem.live_array_bytes(exclude_ids=before) + sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(batch)
    )
    rec = amem.compare_to_measured(plan_on, measured, "live_arrays")
    assert rec["ok"] is True, rec


# -- lint rule ------------------------------------------------------------


def test_act_quant_unconsumed_rule(world8):
    # A loss with no boundary: arming act-quant changes nothing and the
    # WARNING says so.
    def bare_loss(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    params = {"w": jnp.zeros((8, 4), jnp.float32)}
    batch = (jnp.zeros((16, 8), jnp.float32),
             jnp.zeros((16, 4), jnp.float32))
    findings = analysis.lint_traced(
        jax.value_and_grad(aq.checkpoint_fn(bare_loss, "", "int8")),
        (params, batch), params=params, act_quant="int8",
    )
    assert "act-quant-unconsumed" in [f.rule for f in findings]

    # The MLP declares boundaries -> silent.
    mparams, mbatch, mloss = _mlp_setup()

    def armed(p, b):
        with aq.activate("int8"):
            return mloss(p, b)

    findings = analysis.lint_traced(
        jax.value_and_grad(aq.checkpoint_fn(armed, "", "int8")),
        (mparams, mbatch), params=mparams, act_quant="int8",
    )
    assert "act-quant-unconsumed" not in [f.rule for f in findings]


def test_checkpoint_fn_composes_with_base_policy(world8):
    """act-quant + a selective remat policy: the composed policy saves
    the named int8 buffers on top of the base policy's saves, and the
    step still trains."""
    params, batch, loss_fn = _mlp_setup()
    step, opt = dp.make_train_step(
        loss_fn, optax.adamw(1e-2), act_quant="int8",
        remat="dots_saveable",
    )
    state = dp.init_state(jax.tree.map(jnp.array, params), opt)
    l0 = None
    for _ in range(4):
        state, loss = step(state, batch)
        l0 = l0 if l0 is not None else float(loss)
    assert np.isfinite(float(loss)) and float(loss) < l0
