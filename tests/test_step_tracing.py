"""The step names itself: phase scopes and kernel names in the compiled
program, the program's spans on the profiler's clock, the lagged stamps
that replaced the blocking bracket, and the counters that are on by
default (ISSUE 24); the models name their parts (ISSUE 38)."""

import ast
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import model_parts
from conftest import cpu_devices

PHASE_SCOPES = ("hvd_grad", "hvd_reduce", "hvd_update", "hvd_loss_avg")


def _loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


def _state_and_batch(dp, opt):
    state = dp.init_state({"w": jnp.ones((4, 2))}, opt)
    return state, (jnp.ones((8, 4)), jnp.zeros((8, 2)))


@pytest.fixture
def world():
    import horovod_tpu as hvd

    hvd.init(devices=cpu_devices(8))
    yield hvd
    hvd.shutdown()


@pytest.fixture
def planes_off(monkeypatch):
    """Every plane off, whatever the environment says."""
    from horovod_tpu.obs import goodput, registry, trace

    for var in ("HVDTPU_METRICS", "HVDTPU_TRACE", "HVDTPU_GOODPUT"):
        monkeypatch.delenv(var, raising=False)
    registry._enabled = None
    trace._reset_for_tests()
    assert not (registry.enabled() or trace.enabled() or goodput.enabled())
    yield
    registry._enabled = None
    trace._reset_for_tests()


@pytest.fixture
def trace_on(tmp_path):
    from horovod_tpu.obs import trace

    trace._reset_for_tests()
    rec = trace.enable(directory=str(tmp_path), capacity=256)
    yield rec
    trace._reset_for_tests()


# ---- device side: scopes and kernel names --------------------------------


@pytest.mark.parametrize(
    "kwargs", [{}, {"sharded": True}, {"guard": True}],
    ids=["replicated", "sharded", "guarded"],
)
def test_lowered_step_names_its_phases(world, kwargs):
    from horovod_tpu.parallel import dp

    step, opt = dp.make_train_step(_loss, optax.adamw(1e-2), **kwargs)
    state, batch = _state_and_batch(dp, opt)
    text = step.lower(state, batch).as_text(debug_info=True)
    for scope in PHASE_SCOPES:
        assert f'"{scope}' in text, scope
    # the jitted function has a name of its own
    assert "jit_hvd_train_step" in text or "jit(hvd_train_step)" in text


def test_every_pallas_call_has_a_distinct_name():
    path = os.path.join(
        os.path.dirname(__file__), "..", "horovod_tpu", "ops",
        "pallas_kernels.py",
    )
    names = []
    for node in ast.walk(ast.parse(open(path).read())):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "pallas_call"
        ):
            given = [k.value for k in node.keywords if k.arg == "name"]
            # a literal, or ``_kernel_name(<literal>, plan)``: the literal
            # and, for a windowed call, ``_window`` after it
            if given and isinstance(given[0], ast.Call):
                assert given[0].func.id == "_kernel_name"
                given = given[0].args[:1]
            assert given and isinstance(given[0], ast.Constant), (
                f"pallas_call at line {node.lineno} has no literal name="
            )
            names.append(given[0].value)
    assert len(names) == 8
    assert len(set(names)) == len(names), names
    assert {"hvd_flash_fwd", "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"} <= set(
        names
    )


def test_kernel_name_reaches_the_lowered_program(world):
    """``pallas_call(name=)`` opens a scope of that name: the flash
    kernels are told apart by label in the lowered text."""
    from horovod_tpu.ops.pallas_kernels import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def f(q):
        return flash_attention(q, q, q, causal=True).sum()

    text = jax.jit(jax.grad(f)).lower(q).as_text(debug_info=True)
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"):
        assert name in text, name


# ---- device side: the models' parts ---------------------------------------


def _traced_step(world, case):
    """The tiny family's default step, traced: ``(top module, jaxpr)``."""
    from horovod_tpu.parallel import dp

    top, _, loss_fn, params, batch = model_parts.build(case, world.size())
    step, opt = dp.make_train_step(loss_fn, optax.adamw(3e-4))
    state = jax.eval_shape(lambda p: dp.init_state(p, opt), params)
    return top, step.trace(state, batch)


@pytest.mark.parametrize("case", list(model_parts.CASES))
def test_every_operation_of_apply_carries_exactly_one_part(world, case):
    """Inside the model's ``apply`` (the top module's name is in the
    stack) every operation lies under ``hvd_grad`` and under exactly one
    of the seven parts, the expert model's three scopes or the Kimi Delta
    Attention mixer's three, so the readers
    by scope count each operation once; ``mtp`` is the one scope that lies
    over a part; a Mosaic call carries none, so no reader by scope counts
    a kernel that ``flash_ms`` has. An operation with literals only for
    operands is a constant the compiler folds (the zero cotangent that
    ``custom_vjp`` makes for the unused ``lse``): no device time, no rule."""
    top, jaxpr = _traced_step(world, case)
    one_of = (model_parts.PARTS + model_parts.EXPERT_SCOPES
              + model_parts.KDA_SCOPES + model_parts.SELECT_SCOPES
              + model_parts.GDN_SCOPES)
    inside, kernels, seen = 0, 0, set()
    for primitive, stack, computed in model_parts.operations(jaxpr.jaxpr):
        if top not in stack:
            continue
        inside += 1
        segments = stack.split("/")
        assert "hvd_grad" in segments, stack
        held = [scope for scope in one_of if scope in segments]
        if primitive == "pallas_call":
            kernels += 1
            assert not held, (stack, held)
            assert segments[-1].startswith(
                ("hvd_flash_", "hvd_kda_", "hvd_dsa_", "hvd_gdn_")
            ), stack
        elif computed:
            assert len(held) == 1, (primitive, stack, held)
        seen.update(held)
    assert inside > 500
    family, use_flash = model_parts.CASES[case]
    # 3 and 4 blocks; one latent layer's 3 and four KDA layers' 2 each
    # ... two sparse layers' 5 each (select, three masked flash, index loss)
    # ... three Gated DeltaNet layers' 2 each and one full layer's 3
    per_family = {"latent_moe": 9, "window_moe": 12, "linear_moe": 11,
                  "select_moe": 10, "linear_dense": 9}
    assert kernels == (per_family.get(family, 6) if use_flash else 0)
    # each family opens what the table in docs/api.md says it does
    attention = {"attn_layout"} if use_flash else {"attn_xla"}
    if family == "latent_moe":
        want = {"embed", "norm", "mlp", "head", "attn_layout",
                *model_parts.EXPERT_SCOPES} | attention
    elif family == "window_moe":  # no dense feed-forward, so no ``mlp``
        want = {"embed", "norm", "head", "attn_proj", "attn_layout",
                "moe_route", "moe_experts"} | attention
    elif family == "select_moe":  # the window block with its indexer on
        want = {"embed", "norm", "head", "attn_proj", "attn_layout",
                "moe_route", "moe_experts", "index_proj"} | attention
    elif family == "linear_moe":  # no ``attn_proj``: two mixers' own scopes
        want = {"embed", "norm", "mlp", "head", "attn_layout",
                *model_parts.EXPERT_SCOPES,
                *model_parts.KDA_SCOPES} | attention
    elif family == "linear_dense":  # the kernels' entry opens ``kda_conv``
        want = {"embed", "norm", "mlp", "head", "attn_proj", "attn_layout",
                "kda_conv", *model_parts.GDN_SCOPES} | attention
    else:
        want = {"embed", "norm", "mlp", "head", "attn_proj"} | attention
    assert seen == want


@pytest.mark.parametrize(
    "case", ["gpt2-flash", "bert-mlm-flash", "bert-cls-padded",
             "latent-moe-flash", "window-moe-flash"],
)
def test_parts_change_the_traced_step_in_its_names_only(world, case):
    """With no part opened (a patch of ``jax.named_scope``, local to this
    test) the step traces to the same jaxpr, name stacks apart."""
    _, scoped = _traced_step(world, case)
    with model_parts.parts_disabled():
        _, bare = _traced_step(world, case)
        assert not any(
            scope in stack.split("/")
            for _, stack, _ in model_parts.operations(bare.jaxpr)
            for scope in model_parts.PARTS
        )
    assert str(scoped) == str(bare)


@pytest.mark.parametrize(
    "family", ["gpt2", "bert_mlm", "bert_cls", "latent_moe"]
)
def test_parameter_paths_are_the_ones_before_the_parts(family):
    """A scope is no module: the parameter trees are those recorded from
    the tree before the models named their parts (paths and shapes, the
    same builds run on the parent commit)."""
    path = os.path.join(
        os.path.dirname(__file__), "fixtures", "model_param_paths.json"
    )
    with open(path) as f:
        golden = json.load(f)[family]
    case = next(c for c, (fam, _) in model_parts.CASES.items() if fam == family)
    _, _, _, params, _ = model_parts.build(case, 1)
    assert model_parts.param_paths(params) == golden


# ---- host side: the spans on the profiler's clock ------------------------


def test_profile_holds_the_programs_spans(world, planes_off, tmp_path):
    """Three steps under ``jax.profiler.trace`` on the CPU, read back
    with ``ProfileData``: ``hvd.step.dispatch`` contains ``hvd.step.jit``
    and ``hvd.input.fill`` contains ``hvd.input.put``."""
    from jax.profiler import ProfileData
    from horovod_tpu.parallel import dp

    hvd = world
    step, opt = dp.make_train_step(_loss, optax.sgd(0.01))
    state, batch = _state_and_batch(dp, opt)
    host = jax.tree.map(np.asarray, batch)
    batches = hvd.prefetch_to_device(host for _ in range(8))
    for _ in range(2):  # compile outside the trace
        state, loss = step(state, next(batches))
    loss.block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            state, loss = step(state, next(batches))
        loss.block_until_ready()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("hvd."):
                    spans.setdefault(ev.name, []).append((
                        ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats),
                    ))
    assert len(spans["hvd.step.dispatch"]) == 3
    assert len(spans["hvd.step.jit"]) == 3
    assert len(spans["hvd.input.put"]) == 3

    def inside(inner, outer):
        return all(
            any(os_ <= s and e <= oe for os_, oe, _ in spans[outer])
            for s, e, _ in spans[inner]
        )

    assert inside("hvd.step.jit", "hvd.step.dispatch")
    assert inside("hvd.input.put", "hvd.input.fill")
    # the step number rides the annotation as an argument
    assert sorted(a["step"] for _, _, a in spans["hvd.step.dispatch"]) == [
        2, 3, 4
    ]
    assert "hvd.step.sync" not in spans  # no plane on: nothing blocks


# ---- the lagged stamps ---------------------------------------------------


class _Recorded:
    """Stands in for a device array: records who waited for it."""

    def __init__(self, log, tag):
        self.log, self.tag = log, tag

    def block_until_ready(self):
        self.log.append(self.tag)
        return self


def test_wrapper_blocks_only_on_the_previous_loss(trace_on):
    """With a plane on the wrapper dispatches step i, then waits for step
    i-1's loss: never for the state, never for the step it has just
    dispatched. The ring's step events keep their names."""
    from horovod_tpu.parallel import dp

    log, calls = [], []

    def fn(state, batch):
        k = len(calls)
        calls.append(k)
        return _Recorded(log, f"state{k}"), _Recorded(log, f"loss{k}")

    step = dp._instrument_step(fn, None, None)
    waited_after_call = []
    for _ in range(4):
        step(None, None)
        waited_after_call.append(list(log))
    assert waited_after_call == [
        [], ["loss0"], ["loss0", "loss1"], ["loss0", "loss1", "loss2"],
    ]
    names = [ev["name"] for ev in trace_on._ring]
    for name in ("step", "step.host_dispatch", "step.device",
                 "hvd.step.dispatch", "hvd.step.sync"):
        assert name in names, name
    steps = [ev for ev in trace_on._ring if ev["name"] == "step"]
    assert [ev["args"]["step"] for ev in steps] == [0, 1, 2]  # N-1 booked
    for ev in steps:  # the two slices tile the step
        parts = [
            e for e in trace_on._ring
            if e["name"] in ("step.host_dispatch", "step.device")
            and ev["ts"] <= e["ts"] <= ev["ts"] + ev["dur"]
        ]
        assert parts
    assert trace_on.open_spans() == []


def test_wrapper_off_path_blocks_on_nothing(planes_off):
    from horovod_tpu.parallel import dp

    log = []
    step = dp._instrument_step(
        lambda s, b: (_Recorded(log, "state"), _Recorded(log, "loss")),
        None, None,
    )
    for _ in range(3):
        step(None, None)
    assert log == []


# ---- counters that are on by default -------------------------------------


def test_always_on_counters_with_every_plane_off(world, planes_off):
    """``hvd.obs.snapshot()`` holds the step's builds, the jit dispatch
    and the input put with no plane on. On more than one device the
    replicated step places the initial state on the mesh before its first
    dispatch (PR 29), so the second call, which sees the state the first
    returned, finds the program built: the counter says one lowering
    (on one device ROADMAP D1b's second build is still there)."""
    from horovod_tpu.parallel import dp

    hvd = world

    def lowerings():
        return hvd.obs.snapshot()["counters"].get(
            "build.lowerings.hvd_train_step", 0
        )

    def observed(name):
        return hvd.obs.snapshot()["histograms"].get(name, {}).get("count", 0)

    step, opt = dp.make_train_step(_loss, optax.sgd(0.01))
    state, batch = _state_and_batch(dp, opt)
    host = jax.tree.map(np.asarray, batch)
    batches = hvd.prefetch_to_device(host for _ in range(4))
    before, jit_before = lowerings(), observed("step.jit_dispatch_ms")
    put_before = observed("input.put_ms")
    counters_before = hvd.obs.snapshot()["counters"]
    stalled_before = counters_before.get("input.stalled", 0)
    state, _ = step(state, next(batches))
    assert lowerings() - before == 1
    state, _ = step(state, next(batches))
    assert lowerings() - before == 1
    state, loss = step(state, next(batches))
    assert lowerings() - before == 1
    loss.block_until_ready()
    snap = hvd.obs.snapshot()
    assert observed("step.jit_dispatch_ms") - jit_before == 3
    # counts that hold whenever the refills ran: every batch handed out
    # was put, and the first fill found the buffer empty
    assert observed("input.put_ms") - put_before >= 3
    assert snap["counters"]["input.stalled"] - stalled_before >= 1
    assert snap["gauges"]["build.lower_s.hvd_train_step"] > 0
    assert snap["counters"]["build.compiles.hvd_train_step"] >= 1
    # the plane itself stayed off: nothing per-step was booked (an earlier
    # test of this process may have booked steps with the plane on)
    assert snap["counters"].get("step.count") == counters_before.get(
        "step.count"
    )


def test_build_listener_keys_three_names_as_one():
    from horovod_tpu.obs import build

    assert (
        build.function_key("hvd_train_step")
        == build.function_key("jit(hvd_train_step)")
        == build.function_key("jit_hvd_train_step")
        == "hvd_train_step"
    )


def test_flight_ring_says_which_step_call_rebuilt(world, trace_on,
                                                  monkeypatch):
    """Each build is a ring span with its phase, its function and the
    call number of the step wrapper it happened inside."""
    from horovod_tpu.obs import build
    from horovod_tpu.parallel import dp

    monkeypatch.setattr(build, "RING_MIN_S", 0.0)
    step, opt = dp.make_train_step(_loss, optax.sgd(0.01))
    state, batch = _state_and_batch(dp, opt)
    for _ in range(3):
        state, loss = step(state, batch)
    jax.block_until_ready(loss)
    builds = [
        ev["args"] for ev in trace_on._ring
        if ev["name"] == "hvd.build" and ev["args"]["fn"] == "hvd_train_step"
    ]
    lowered_in = [a["step_call"] for a in builds if a["phase"] == "lower"]
    assert lowered_in == [0]  # the first call only: the state was placed
    assert {a["phase"] for a in builds} == {"trace", "lower", "compile"}


def test_prefetch_spans_close_before_the_yield(world, trace_on):
    hvd = world
    host = (np.ones((8, 4), np.float32), np.zeros((8, 2), np.float32))
    batches = hvd.prefetch_to_device((host for _ in range(3)), depth=2)
    next(batches)
    assert trace_on.open_spans() == []
    fills = [ev for ev in trace_on._ring if ev["name"] == "hvd.input.fill"]
    puts = [ev for ev in trace_on._ring if ev["name"] == "hvd.input.put"]
    assert len(fills) == 1 and len(puts) == 2
    assert fills[0]["args"] == {"stalled": True, "occupancy": 0, "depth": 2}
    for p in puts:
        assert fills[0]["ts"] <= p["ts"]
        assert p["ts"] + p["dur"] <= fills[0]["ts"] + fills[0]["dur"]
    assert len(list(batches)) == 2  # as long as its input
    later = [ev for ev in trace_on._ring if ev["name"] == "hvd.input.fill"]
    assert [ev["args"]["stalled"] for ev in later] == [True, False, False]
