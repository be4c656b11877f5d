"""The seam between a gradient and its update on a world of one (PR 52).

Above one device the all-reduce stands between a weight's gradient and its
update. On one, ``ops/fusion.update_seams`` keeps that seam for large
leaves with ``lax.optimization_barrier`` (the identity), so everything
here is about structure and bit-equality; what the seam is worth in time
and memory is read on the chip and in the compiled plan (PERF.md).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from conftest import cpu_devices
from horovod_tpu.obs import registry
from horovod_tpu.ops import fusion
from horovod_tpu.parallel import dp

# leaves on both sides of the patched constant: ``wide`` and ``tall`` hold
# 2,048 elements each, the rest fewer
SEAM_AT = 2048
NEVER = 2 ** 62


def _params():
    rng = np.random.RandomState(0)
    return {
        "wide": jnp.asarray(rng.randn(32, 64) * 0.1, jnp.float32),
        "tall": jnp.asarray(rng.randn(64, 32) * 0.1, jnp.float32),
        "small": jnp.asarray(rng.randn(32, 8) * 0.1, jnp.float32),
        "bias": jnp.zeros((64,), jnp.float32),
    }


def _loss(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["wide"] + params["bias"])
    return jnp.mean((jnp.tanh(h @ params["tall"]) @ params["small"] - y) ** 2)


def _batch(seed, n=16):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(n, 32), jnp.float32),
        jnp.asarray(rng.randn(n, 8), jnp.float32),
    )


@pytest.fixture
def world_of():
    """``world_of(n)`` initialises a flat world of ``n`` CPU devices."""
    def init(n):
        return hvd.init(devices=cpu_devices(n))

    yield init
    hvd.shutdown()


def _seam_at(monkeypatch, min_size):
    """The constant patched: leaves of ``min_size`` elements are large."""
    monkeypatch.setattr(fusion, "UPDATE_SEAM_MIN_SIZE", min_size)


def _train(min_size, monkeypatch, steps=3, **step_kwargs):
    """Three steps of the small model with the constant at ``min_size``:
    the final state and every loss, as numpy."""
    _seam_at(monkeypatch, min_size)
    step, wrapped = dp.make_train_step(
        _loss, optax.adamw(1e-2), **step_kwargs
    )
    state = dp.init_state(_params(), wrapped)
    losses = []
    for i in range(steps):
        state, loss = step(state, _batch(i))
        losses.append(np.asarray(loss))
    return jax.tree.map(np.asarray, (state.params, state.opt_state)), losses


def _barrier_arities(closed_jaxpr):
    """Operand counts of every ``optimization_barrier`` of a traced
    function, nested jaxprs included, in trace order."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "optimization_barrier":
                found.append(len(eqn.invars))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (
                    value,
                ):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(closed_jaxpr.jaxpr)
    return found


def _barriers(step, state, batch):
    return _barrier_arities(
        jax.make_jaxpr(step._mapped_for(state))(state, batch)
    )


def _traced(min_size, monkeypatch, **step_kwargs):
    _seam_at(monkeypatch, min_size)
    step, wrapped = dp.make_train_step(
        _loss, optax.adamw(1e-2), **step_kwargs
    )
    state = dp.init_state(_params(), wrapped)
    n = hvd.size()
    return _barriers(step, state, _batch(0, n=16 * n))


def _gauges():
    reg = registry.always()
    return (
        reg.gauge("fusion.update_seams").get(),
        reg.gauge("fusion.update_seam_bytes").get(),
    )


# -- (a) the identity ---------------------------------------------------------


def test_seams_leave_every_bit_where_it_was(world_of, monkeypatch):
    """Parameters, moments and losses of three steps are bit-equal with
    the seams on and with the constant out of reach."""
    world_of(1)
    seamed, seamed_losses = _train(SEAM_AT, monkeypatch)
    assert _gauges()[0] == 2
    plain, plain_losses = _train(NEVER, monkeypatch)
    assert _gauges() == (0, 0)
    for a, b in zip(jax.tree.leaves(seamed), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(seamed_losses, plain_losses)


# -- (b) where the barriers stand ---------------------------------------------


def test_one_barrier_a_large_leaf_on_a_world_of_one(world_of, monkeypatch):
    """The wide leaf's barrier takes its gradient and its weight; the
    tall leaf's gradient passes alone; small leaves pass as they are."""
    world_of(1)
    assert sorted(_traced(SEAM_AT, monkeypatch)) == [1, 2]
    assert _traced(NEVER, monkeypatch) == []


def _one_matrix_barriers(shape):
    """The barriers of a step whose parameters are one matrix."""
    def loss(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2)

    step, wrapped = dp.make_train_step(loss, optax.adamw(1e-2))
    state = dp.init_state({"w": jnp.ones(shape)}, wrapped)
    return _barriers(step, state, jnp.ones((8, shape[0])))


@pytest.mark.parametrize(
    "shape,operands",
    [
        ((32, 64), [2]),    # widens by two: kept apart, the weight rides
        ((16, 64), [2]),    # by four, the bound
        ((64, 32), [1]),    # narrows: kept apart, the gradient alone
        ((16, 128), []),    # by eight, a vocabulary projection: as it is
        ((128, 16), []),    # a lookup table: as it is
    ],
)
def test_the_rule_reads_the_leaf_alone(
    world_of, monkeypatch, shape, operands
):
    """A step's one large leaf gets its seam or not by its own size and
    shape, never by how many such leaves the step holds."""
    world_of(1)
    _seam_at(monkeypatch, shape[0] * shape[1])
    assert _one_matrix_barriers(shape) == operands
    assert _gauges() == (
        len(operands), len(operands) * shape[0] * shape[1] * 4
    )


def test_a_stack_keeps_its_program(world_of, monkeypatch):
    """A leaf of three dimensions (a stack of experts) passes as it is,
    whatever its size: no cell has read what a seam does to a batched
    dW."""
    world_of(1)
    _seam_at(monkeypatch, SEAM_AT)

    def loss(p, batch):
        return jnp.mean(jnp.einsum("bi,eio->beo", batch, p["w"]) ** 2)

    step, wrapped = dp.make_train_step(loss, optax.adamw(1e-2))
    state = dp.init_state({"w": jnp.ones((2, 32, 64))}, wrapped)
    assert _barriers(step, state, jnp.ones((8, 32))) == []


def test_the_default_constant_is_out_of_a_small_models_reach(world_of):
    world_of(1)
    assert fusion.UPDATE_SEAM_MIN_SIZE == 2 ** 25
    step, wrapped = dp.make_train_step(_loss, optax.adamw(1e-2))
    state = dp.init_state(_params(), wrapped)
    assert _barriers(step, state, _batch(0)) == []


@pytest.mark.parametrize("world", [4, 8])
def test_no_barrier_where_the_exchange_stands(world_of, monkeypatch, world):
    world_of(world)
    assert _traced(SEAM_AT, monkeypatch) == []
    assert _gauges() == (0, 0)


def test_no_barrier_on_the_sharded_path(world_of, monkeypatch):
    world_of(1)
    assert _traced(SEAM_AT, monkeypatch, sharded=True) == []


def test_a_barrier_never_spans_two_leaves(world_of, monkeypatch):
    """Two large wide leaves get a barrier each: one over both would hold
    both gradients alive at once."""
    world_of(1)
    _seam_at(monkeypatch, SEAM_AT)
    params = {"a": jnp.ones((32, 64)), "b": jnp.ones((32, 64))}

    def loss(p, batch):
        return jnp.mean((batch @ p["a"] + batch @ p["b"]) ** 2)

    step, wrapped = dp.make_train_step(loss, optax.adamw(1e-2))
    state = dp.init_state(params, wrapped)
    assert _barriers(step, state, jnp.ones((8, 32))) == [2, 2]


def test_the_weight_rides_only_where_it_is_given(world_of, monkeypatch):
    """A wide leaf's barrier takes the weight the update is given,
    whatever the inner optimizer keeps beside it; without ``params`` the
    gradient passes alone."""
    world_of(1)
    _seam_at(monkeypatch, SEAM_AT)
    params = {"a": jnp.ones((32, 64)), "b": jnp.ones((32, 64))}

    def arity(optimizer, with_params):
        dopt = hvd.DistributedOptimizer(optimizer)

        @hvd.spmd(in_specs=(hvd.P(),), out_specs=hvd.P())
        def update(p):
            state = dopt.init(p)
            grads = jax.tree.map(lambda x: x * 2.0, p)
            updates, _ = dopt.update(
                grads, state, p if with_params else None
            )
            return updates

        return _barrier_arities(jax.make_jaxpr(update)(params))

    assert arity(optax.adam(1e-2), True) == [2, 2]
    assert arity(optax.adam(1e-2), False) == [1, 1]
    assert arity(optax.sgd(1e-2), True) == [2, 2]
    assert arity(optax.sgd(1e-2, momentum=0.9), False) == [1, 1]


def test_adasum_keeps_its_program(world_of, monkeypatch):
    world_of(1)
    _seam_at(monkeypatch, SEAM_AT)
    dopt = hvd.DistributedOptimizer(optax.sgd(1e-2), op=hvd.Adasum)
    params = {"a": jnp.ones((32, 64))}

    @hvd.spmd(in_specs=(hvd.P(),), out_specs=hvd.P())
    def update(p):
        updates, _ = dopt.update(p, dopt.init(p), p)
        return updates

    assert _traced(SEAM_AT, monkeypatch) and _gauges()[0] == 2
    assert "optimization_barrier" not in str(jax.make_jaxpr(update)(params))
    assert _gauges() == (0, 0)


# -- (c) the gauges -------------------------------------------------------------


def test_gauges_count_the_seams_of_the_last_trace(world_of, monkeypatch):
    world_of(1)
    arities = _traced(SEAM_AT, monkeypatch)
    # two leaves of 2,048 float32 elements each
    assert _gauges() == (len(arities), 2 * 2048 * 4)


@pytest.mark.parametrize(
    "step_kwargs",
    [{"sharded": True}, {"compression": hvd.Compression.int8}],
    ids=["sharded", "quantized"],
)
def test_a_path_without_seams_resets_the_gauges(
    world_of, monkeypatch, step_kwargs
):
    """The sharded and the quantized update keep no seam, and say so: the
    gauges never carry an earlier trace's count."""
    world_of(1)
    assert _traced(SEAM_AT, monkeypatch) and _gauges()[0] == 2
    assert _traced(SEAM_AT, monkeypatch, **step_kwargs) == []
    assert _gauges() == (0, 0)


# -- (d) the seam is one place: every way into the update passes it -------------


def test_accum_steps_trains_to_the_plain_steps_parameters(
    world_of, monkeypatch
):
    world_of(1)
    seamed, _ = _train(SEAM_AT, monkeypatch, accum_steps=2)
    assert _gauges()[0] == 2
    plain, _ = _train(NEVER, monkeypatch)
    for a, b in zip(jax.tree.leaves(seamed[0]), jax.tree.leaves(plain[0])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_backward_passes_per_step_trains_to_the_plain_steps_parameters(
    world_of, monkeypatch
):
    """Two passes a step over half a batch each, averaged, end where the
    plain step over the whole batch ends; the sync branch holds the
    seams."""
    world_of(1)
    x, y = _batch(0)

    def run(min_size, bpps):
        _seam_at(monkeypatch, min_size)
        dopt = hvd.DistributedOptimizer(
            optax.adamw(1e-2), backward_passes_per_step=bpps,
            average_aggregated_gradients=True,
        )

        @hvd.spmd(in_specs=(hvd.P(),) * 3, out_specs=hvd.P())
        def train(p, x, y):
            state = dopt.init(p)
            for part in range(bpps):
                rows = slice(part * 16 // bpps, (part + 1) * 16 // bpps)
                grads = jax.grad(_loss)(p, (x[rows], y[rows]))
                updates, state = dopt.update(grads, state, p)
                p = optax.apply_updates(p, updates)
            return p

        arities = _barrier_arities(jax.make_jaxpr(train)(_params(), x, y))
        return jax.tree.map(np.asarray, train(_params(), x, y)), arities

    seamed, arities = run(SEAM_AT, 2)
    # both passes trace the sync branch: the wide and the tall leaf in each
    assert sorted(arities) == [1, 1, 2, 2]
    plain, arities = run(NEVER, 1)
    assert arities == []
    for a, b in zip(jax.tree.leaves(seamed), jax.tree.leaves(plain)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
