"""``dp._resolve``: the arguments of ``make_train_step`` as given ->
options as resolved, one table.

For every defaulted argument that ``parallel/dp.py`` resolves (itself or
through ``tune.resolve`` / ``guard.resolve`` / ``actquant.resolve_mode``):
the argument and its ``HVDTPU_*`` twin resolve to the same options, an
explicit argument beats the environment, and unset with an empty
environment gives the default the docstring states. Then one case per
validation error, by message. Nothing here traces or compiles.

``fused_update`` / ``HVDTPU_FUSED_UPDATE`` and ``threshold_bytes`` /
``HVDTPU_FUSION_THRESHOLD`` are left out: ``optimizer.py`` resolves them,
inside closures of the built optimizer that only a trace reads
(``tests/test_fused_update.py``, ``tests/test_fusion.py``).
"""

import ast
import inspect

import optax
import pytest

from horovod_tpu import Compression
from horovod_tpu.ops.collectives import Average, Sum
from horovod_tpu.parallel import dp
from horovod_tpu.utils import env as _env

EMPTY = inspect.Parameter.empty

# The signature as the parent commit of PR 46 had it: names, order,
# defaults. A refactor of the builder changes none of them.
SIGNATURE = [
    ("loss_fn", EMPTY), ("optimizer", EMPTY), ("has_aux", False),
    ("distribute_optimizer", True), ("op", Average), ("compression", None),
    ("axis", None), ("donate", True), ("mesh", None), ("batch_spec", None),
    ("sharded", False), ("gather_compression", Compression.none),
    ("threshold_bytes", None), ("tokens_per_step", None),
    ("flops_per_step", None), ("overlap", None), ("accum_steps", None),
    ("stagger", None), ("lint", None), ("lint_allow", ()),
    ("error_feedback", True), ("guard", None), ("fused_update", None),
    ("remat", None), ("compute_dtype", None), ("act_quant", None),
    ("autotune", None), ("publish", None),
]


def _wire(o):
    spec = getattr(o.compression, "spec", None)
    return (
        o.quantized, getattr(spec, "name", ""),
        o.compression.block_size() if o.quantized else None,
    )


# argument, its twin, a value, the twin's text for the same value, an
# explicit value that contradicts that text, what the options show of it,
# the default the docstring states, arguments held fixed beside it
TWINS = [
    ("compression", _env.QUANT, Compression.int8, "int8", Compression.none,
     _wire, (False, "", None), {}),
    ("overlap", _env.OVERLAP, True, "1", False,
     lambda o: (o.overlap, o.stagger), (False, False), {}),
    ("accum_steps", _env.OVERLAP_ACCUM_STEPS, 4, "4", 2,
     lambda o: o.accum_steps, 1, {}),
    ("stagger", _env.OVERLAP_STAGGER, False, "0", True,
     lambda o: o.stagger, True, {"overlap": True}),
    ("lint", _env.LINT, "raise", "raise", False,
     lambda o: o.lint, "", {}),
    ("guard", _env.GUARD, True, "1", False,
     lambda o: o.guard, None, {}),
    ("remat", _env.REMAT, "dots_saveable", "dots_saveable", "full",
     lambda o: o.remat, "", {}),
    ("compute_dtype", _env.COMPUTE_DTYPE, "fp8", "fp8", "",
     lambda o: o.compute_dtype, "", {}),
    ("act_quant", _env.ACT_QUANT, "int8", "int8", "",
     lambda o: o.act_quant, "", {}),
    ("autotune", _env.AUTOTUNE, True, "1", False,
     lambda o: type(o.autotune).__name__, "NoneType", {}),
    ("publish", _env.PUBLISH_EVERY, 5, "5", 2,
     lambda o: o.publish, 0, {}),
]
IDS = [row[0] for row in TWINS]


@pytest.fixture
def empty_env(world8, monkeypatch):
    for name in [row[1] for row in TWINS] + [_env.QUANT_BLOCK]:
        for prefix in ("HVDTPU_", "HOROVOD_"):
            monkeypatch.delenv(prefix + name, raising=False)
    return monkeypatch


def _given(**kwargs):
    """The record ``make_train_step`` makes of a call's arguments."""
    defaults = dict(SIGNATURE[2:])
    return dp._StepArgs(
        loss_fn=lambda params, batch: 0.0, optimizer=optax.sgd(0.1),
        **{**defaults, **kwargs},
    )


def test_signature_is_the_parents():
    params = inspect.signature(dp.make_train_step).parameters
    assert [(n, p.default) for n, p in params.items()] == SIGNATURE
    kinds = [p.kind for p in params.values()]
    assert kinds[:2] == [inspect.Parameter.POSITIONAL_OR_KEYWORD] * 2
    assert set(kinds[2:]) == {inspect.Parameter.KEYWORD_ONLY}
    # the record of a call is cut from the signature, field for field
    names = [n for n, _ in SIGNATURE]
    assert [f.name for f in dp.dataclasses.fields(dp._StepArgs)] == names
    # and the options as resolved are the same fields and five more
    assert [f.name for f in dp.dataclasses.fields(dp._Options)] == names + [
        "quantized", "world_axes", "overlapped_exchange", "reduction_limit",
        "copts",
    ]


@pytest.mark.parametrize("row", TWINS, ids=IDS)
def test_argument_and_twin_resolve_alike(empty_env, row):
    name, twin, value, text, _, shows, default, fixed = row
    by_argument = shows(dp._resolve(_given(**{name: value}, **fixed)))
    empty_env.setenv("HVDTPU_" + twin, text)
    by_twin = shows(dp._resolve(_given(**fixed)))
    assert by_argument == by_twin != default


@pytest.mark.parametrize("row", TWINS, ids=IDS)
def test_explicit_argument_beats_the_environment(empty_env, row):
    name, twin, _, text, other, shows, _, fixed = row
    alone = shows(dp._resolve(_given(**{name: other}, **fixed)))
    empty_env.setenv("HVDTPU_" + twin, text)
    by_twin = shows(dp._resolve(_given(**fixed)))
    assert shows(dp._resolve(_given(**{name: other}, **fixed))) == alone
    assert alone != by_twin


@pytest.mark.parametrize("row", TWINS, ids=IDS)
def test_unset_and_empty_environment_give_the_default(empty_env, row):
    *_, shows, default, fixed = row
    assert shows(dp._resolve(_given(**fixed))) == default


FAULTS = [
    ({"accum_steps": 0}, ValueError, "accum_steps must be >= 1, got 0"),
    ({"lint": "error"}, ValueError, "lint must be one of False/'off'"),
    ({"compute_dtype": "fp4"}, ValueError,
     "compute_dtype='fp4' is not recognized"),
    ({"act_quant": "int4"}, ValueError, "act_quant='int4' is not recognized"),
    ({"compute_dtype": "fp8", "sharded": True}, NotImplementedError,
     "compute_dtype='fp8' is replicated-path only"),
    ({"compute_dtype": "fp8", "op": Sum}, ValueError,
     "compute_dtype='fp8' requires op=Average"),
    ({"remat": "dots"}, ValueError, "unknown remat policy 'dots'"),
    ({"guard": "yes"}, ValueError, "guard must be None/True/False"),
    ({"fused_update": True}, ValueError,
     "fused_update requires the ZeRO-1 flat-shard layout"),
    ({"autotune": "yes"}, ValueError,
     "autotune must be None/bool/AutotuneConfig"),
    # two faults: the one the builder met first is the one raised
    ({"accum_steps": 0, "lint": "error", "guard": "yes"}, ValueError,
     "accum_steps must be >= 1"),
    ({"remat": "dots", "guard": "yes", "fused_update": True}, ValueError,
     "unknown remat policy"),
]


@pytest.mark.parametrize(
    "kwargs,error,message", FAULTS,
    ids=["+".join(f[0]) for f in FAULTS],
)
def test_each_fault_by_message(empty_env, kwargs, error, message):
    with pytest.raises(error, match=message):
        dp._resolve(_given(**kwargs))
    # and through the front door, where nothing is built before it
    with pytest.raises(error, match=message):
        dp.make_train_step(
            lambda params, batch: 0.0, optax.sgd(0.1), **kwargs
        )


def test_a_twin_that_names_no_wire_is_refused(empty_env):
    empty_env.setenv("HVDTPU_QUANT", "int4")
    with pytest.raises(ValueError, match="HVDTPU_QUANT='int4'"):
        dp._resolve(_given())


def test_a_quantized_wire_has_its_block_pinned(empty_env):
    """The optimizer's residual layout and the lint's prediction read
    one block size: the one the environment held at resolve time."""
    empty_env.setenv("HVDTPU_QUANT_BLOCK", "128")
    o = dp._resolve(_given(compression=Compression.int8))
    empty_env.setenv("HVDTPU_QUANT_BLOCK", "512")
    assert o.compression.block_size() == 128


# -- the builder's boxes and their one-way arrows ----------------------------


def _functions():
    tree = ast.parse(inspect.getsource(dp))
    return {
        node.name: node for node in tree.body
        if isinstance(node, ast.FunctionDef)
    }


def test_the_program_is_built_without_the_host_planes():
    """What builds the traced and compiled program imports ``ops/``,
    the optimizer and the in-graph gradient check, and none of the planes
    that hook a step from outside."""
    planes = ("analysis", "tune", "stream", "elastic", "obs")
    fns = _functions()
    for name in ("_build_program", "_program_per_structure",
                 "_build_optimizer", "accumulate_gradients"):
        for node in ast.walk(fns[name]):
            if isinstance(node, ast.ImportFrom):
                module = node.module or ""
                assert module.split(".")[0] not in planes, (name, module)
                if module == "guard" or not module:
                    assert {a.name for a in node.names} <= {
                        "check_gradients", "actquant",
                    }, (name, [a.name for a in node.names])
            assert not isinstance(node, ast.Import), name


def test_defaults_are_read_in_one_function():
    """``_env.<reader>()`` calls in ``dp.py``: all in ``_resolve``, but
    ``HVDTPU_CERT`` on the first call and ``HVDTPU_HBM_BUDGET_GB`` on each
    ``step.lint``, which are read when they are for a reason."""
    elsewhere = {}
    for name, fn in _functions().items():
        reads = {
            node.func.attr for node in ast.walk(fn)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "_env"
        }
        if reads and name != "_resolve":
            elsewhere[name] = reads
    assert elsewhere == {
        "_wrap": {"cert_mode"}, "_static_surfaces": {"hbm_budget_bytes"},
    }
    assert "build_kwargs" not in inspect.getsource(dp)
