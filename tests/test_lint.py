"""Trace-time SPMD linter (``horovod_tpu.analysis``).

Two halves, mirroring the linter's contract:

* **each rule fires** on a deliberately broken step (undeclared axis,
  rank-dependent collective, RS without AG, bf16 accumulator, donated
  buffer read after its update, fusion-parity break, low-precision
  reduction) — a rule that can't fire protects nothing;
* **the clean sweep is clean**: every bundled model, replicated +
  sharded + sharded/overlap builds, zero findings — the CI gate
  (``tools/run_lints.py``) the fast tier runs end to end.
"""

import warnings

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.analysis import (
    LintError,
    Severity,
    apply_allowlist,
    compare_collectives,
    lint_traced,
    trace_collectives,
)
from horovod_tpu.ops.fusion import (
    bucket_byte_layout,
    fused_allreduce,
    fused_reducescatter,
    pack,
)


PARAMS = {"w": jnp.zeros((8, 8)), "b": jnp.zeros((8,))}
BATCH = jnp.zeros((32, 8))


def _loss(p, b):
    return jnp.sum(b @ p["w"] + p["b"])


def _mapped(world8, fn, out_specs=P()):
    return jax.shard_map(
        fn,
        mesh=world8.mesh,
        in_specs=(P(), P("hvd")),
        out_specs=out_specs,
        check_vma=False,
    )


def _rules(findings):
    return sorted({f.rule for f in findings})


class TestRulesFire:
    """Each rule family on a seeded-broken step."""

    def test_undeclared_axis(self, world8):
        def step(p, b):
            return fused_allreduce(jax.grad(_loss)(p, b))["w"]

        f = lint_traced(
            _mapped(world8, step), (PARAMS, BATCH), declared_axes={"data"}
        )
        assert _rules(f) == ["undeclared-axis"]
        assert all(x.severity == Severity.ERROR for x in f)

    def test_rank_dependent_collective(self, world8):
        def step(p, b):
            idx = jax.lax.axis_index("hvd")
            g = jax.grad(_loss)(p, b)
            return jax.lax.cond(
                idx < 4,
                lambda t: fused_allreduce(t)["w"],
                lambda t: t["w"],
                g,
            )

        f = lint_traced(
            _mapped(world8, step), (PARAMS, BATCH), declared_axes={"hvd"}
        )
        assert "rank-dependent-collective" in _rules(f)

    def test_collective_inside_accumulation_loop(self, world8):
        # The anti-pattern the overlap pipeline exists to avoid: a fused
        # reduction INSIDE the microbatch loop (wire bytes scale with K).
        def step(p, b):
            def body(i, pp):
                g = fused_allreduce(jax.grad(_loss)(pp, b))
                return jax.tree.map(lambda x, gg: x - 0.1 * gg, pp, g)

            return jax.lax.fori_loop(0, 4, body, p)["w"]

        f = lint_traced(
            _mapped(world8, step),
            (PARAMS, BATCH),
            declared_axes={"hvd"},
            params=PARAMS,
            world=8,
        )
        assert "collective-in-control-flow" in _rules(f)
        # ... and fusion parity fails too: no top-level fused reduction
        # matches the predicted bucket.
        assert "fusion-parity" in _rules(f)

    def test_rs_without_ag(self, world8):
        def step(p, b):
            shards, _ = fused_reducescatter(jax.grad(_loss)(p, b))
            return sum(s.sum() for s in shards.buffers)

        f = lint_traced(
            _mapped(world8, step), (PARAMS, BATCH), declared_axes={"hvd"}
        )
        assert "rs-without-ag" in _rules(f)

    def test_low_precision_accumulator(self, world8):
        # bf16 running sum in a fori_loop carry — the rounding bug
        # dp.accumulate_gradients' fp32 accumulation exists to avoid.
        def step(p, b):
            def body(i, acc):
                g = jax.grad(_loss)(p, b)
                return jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.bfloat16), acc, g
                )

            acc = jax.lax.fori_loop(
                0,
                4,
                body,
                jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.bfloat16), p
                ),
            )
            return fused_allreduce(
                jax.tree.map(lambda a: a.astype(jnp.float32), acc)
            )["w"]

        f = lint_traced(
            _mapped(world8, step), (PARAMS, BATCH), declared_axes={"hvd"}
        )
        assert "low-precision-accumulator" in _rules(f)

    def test_low_precision_collective_and_allowlist(self, world8):
        def step(p, b):
            g = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), jax.grad(_loss)(p, b)
            )
            g = fused_allreduce(g)
            return g["w"].astype(jnp.float32)

        args = (PARAMS, BATCH)
        f = lint_traced(_mapped(world8, step), args, declared_axes={"hvd"})
        assert _rules(f) == ["low-precision-collective"]
        # Explicit opt-in (what compression= does) suppresses it...
        assert not lint_traced(
            _mapped(world8, step),
            args,
            declared_axes={"hvd"},
            allow_low_precision_collectives=True,
        )
        # ...and so does the allowlist, by rule id or rule:fragment.
        assert not lint_traced(
            _mapped(world8, step),
            args,
            declared_axes={"hvd"},
            allowlist=("low-precision-collective",),
        )
        assert not apply_allowlist(f, ("low-precision-collective:psum",))
        assert apply_allowlist(f, ("low-precision-collective:nomatch",))

    def test_donated_read_after_update(self, world8):
        def step(p, b):
            g = fused_allreduce(jax.grad(_loss)(p, b))
            new_p = jax.tree.map(lambda x, gg: x - 0.1 * gg, p, g)
            drift = jnp.vdot(p["w"], new_p["w"])  # old p after update
            return new_p, drift

        f = lint_traced(
            _mapped(world8, step, out_specs=(P(), P())),
            (PARAMS, BATCH),
            donate_argnums=(0,),
            declared_axes={"hvd"},
        )
        assert "donated-read-after-update" in _rules(f)
        (finding,) = [
            x for x in f if x.rule == "donated-read-after-update"
        ]
        assert "arg0['w']" in finding.message

    def test_donation_dropped(self, world8):
        def step(p, b):
            return fused_allreduce(jax.grad(_loss)(p, b))["w"]

        # Donating the batch, which has no same-shaped output to alias.
        f = lint_traced(
            _mapped(world8, step),
            (PARAMS, BATCH),
            donate_argnums=(1,),
            declared_axes={"hvd"},
        )
        assert "donation-dropped" in _rules(f)

    def test_fusion_parity_break(self, world8):
        # The policy predicts one psum per gradient leaf (what the
        # replicated exchange reaches the jaxpr as); the step reduces
        # only part of the tree and leaves ``b`` unreduced.
        def step(p, b):
            grads = jax.grad(_loss)(p, b)
            return fused_allreduce({"w": grads["w"]})["w"]

        f = lint_traced(
            _mapped(world8, step),
            (PARAMS, BATCH),
            declared_axes={"hvd"},
            params=PARAMS,
            world=8,
        )
        assert "fusion-parity" in _rules(f)

    def test_collective_order_divergence(self, world8):
        def one_bucket(p, b):
            return fused_allreduce(jax.grad(_loss)(p, b))["w"]

        def two_buckets(p, b):
            # Not a bucket boundary any more (every leaf is its own psum
            # in the jaxpr whatever the threshold): a build that reduces
            # one leaf of the two.
            grads = jax.grad(_loss)(p, b)
            return fused_allreduce({"w": grads["w"]})["w"]

        same = compare_collectives(
            _mapped(world8, one_bucket),
            (PARAMS, BATCH),
            _mapped(world8, one_bucket),
            (PARAMS, BATCH),
        )
        assert not same
        diverged = compare_collectives(
            _mapped(world8, one_bucket),
            (PARAMS, BATCH),
            _mapped(world8, two_buckets),
            (PARAMS, BATCH),
        )
        assert _rules(diverged) == ["collective-order-divergence"]


class TestBucketByteLayout:
    """The metadata-only twin of pack() the parity pass trusts."""

    def test_matches_pack(self, world8):
        tree = {
            "a": jnp.zeros((16, 4)),
            "b": jnp.zeros((7,)),
            "c": jnp.zeros((3, 3), jnp.int32),
        }
        layout = dict(bucket_byte_layout(tree, pad_multiple=8))
        buffers, spec = pack(tree, pad_multiple=8)
        for buf in buffers:
            assert layout[str(buf.dtype)] == buf.size * buf.dtype.itemsize

    def test_abstract_leaves(self):
        tree = {
            "a": jax.ShapeDtypeStruct((16, 4), jnp.float32),
            "b": jax.ShapeDtypeStruct((7,), jnp.float32),
        }
        assert bucket_byte_layout(tree) == [("float32", 284)]
        assert bucket_byte_layout(tree, pad_multiple=8) == [
            ("float32", 288)
        ]

    def test_threshold_splits(self):
        tree = [jax.ShapeDtypeStruct((8,), jnp.float32) for _ in range(4)]
        assert len(bucket_byte_layout(tree, 32)) == 4
        assert len(bucket_byte_layout(tree, 1 << 20)) == 1


class TestMakeTrainStepHook:
    """The dp.make_train_step(lint=) surface."""

    def _mlp(self):
        from horovod_tpu.models import MLP

        model = MLP(features=(16,))

        def loss_fn(params, batch):
            x, y = batch
            logits = model.apply({"params": params}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y
            ).mean()

        params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 784)))[
            "params"
        ]
        batch = (jnp.zeros((32, 784)), jnp.zeros((32,), jnp.int32))
        return loss_fn, params, batch

    def test_step_exposes_lint(self, world8):
        from horovod_tpu.parallel import dp

        loss_fn, params, batch = self._mlp()
        for sharded in (False, True):
            step, opt = dp.make_train_step(
                loss_fn, optax.adam(1e-3), sharded=sharded
            )
            state = dp.init_state(params, opt)
            assert step.lint(state, batch) == ()

    def test_lint_raise_aborts_before_dispatch(self, world8):
        from horovod_tpu.parallel import dp

        def bad_loss(params, batch):
            x, y = batch
            del y
            # bf16 loss -> the world-average psum rounds on the wire.
            return jnp.sum(x @ params["w"]).astype(jnp.bfloat16)

        step, opt = dp.make_train_step(
            bad_loss, optax.sgd(0.1), lint="raise"
        )
        params = {"w": jnp.ones((8, 4))}
        state = dp.init_state(params, opt)
        batch = (jnp.zeros((32, 8)), jnp.zeros((32,), jnp.int32))
        with pytest.raises(LintError) as ei:
            step(state, batch)
        assert "low-precision-collective" in str(ei.value)

    def test_lint_warn_and_allow(self, world8):
        from horovod_tpu.parallel import dp

        def bad_loss(params, batch):
            x, y = batch
            del y
            return jnp.sum(x @ params["w"]).astype(jnp.bfloat16)

        batch = (jnp.zeros((32, 8)), jnp.zeros((32,), jnp.int32))

        step, opt = dp.make_train_step(
            bad_loss, optax.sgd(0.1), lint="warn"
        )
        state = dp.init_state({"w": jnp.ones((8, 4))}, opt)
        with pytest.warns(UserWarning, match="low-precision-collective"):
            step(state, batch)

        # Allowlisted: same build runs silently. Fresh state: the first
        # step call above donated its buffers.
        step, opt = dp.make_train_step(
            bad_loss,
            optax.sgd(0.1),
            lint="raise",
            lint_allow=("low-precision-collective",),
        )
        state = dp.init_state({"w": jnp.ones((8, 4))}, opt)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            step(state, batch)

    def test_env_knob_default(self, world8, monkeypatch):
        from horovod_tpu.utils import env as _env

        monkeypatch.setenv("HVDTPU_LINT", "raise")
        assert _env.lint_mode() == "raise"
        monkeypatch.setenv("HVDTPU_LINT", "1")
        assert _env.lint_mode() == "warn"
        monkeypatch.setenv("HVDTPU_LINT", "off")
        assert _env.lint_mode() == ""


class TestCleanSweep:
    """Every bundled model lints clean — the CI gate."""

    def test_run_lints_gate(self, world8):
        import tools.run_lints as run_lints

        report = run_lints.run_all()
        assert report["gates"]["env"]["ok"], report["gates"]["env"]
        assert report["gates"]["docs"]["ok"], report["gates"]["docs"]
        assert report["gates"]["thread"]["ok"], report["gates"]["thread"]
        spmd = report["gates"]["spmd"]
        assert spmd["ok"], spmd
        # The sweep really covered the zoo, seven variants per model
        # (replicated, sharded, sharded+overlap, quantized wire, fused
        # optimizer update, fp8 matmuls, int8 activation storage).
        from horovod_tpu.analysis import harness

        assert set(spmd["models"]) == set(harness.SWEEP_MODELS)
        for variants in spmd["models"].values():
            assert len(variants) == len(harness.SWEEP_VARIANTS) == 7
            assert "replicated+quant-int8" in variants
            assert "sharded+fused-update" in variants
            assert "replicated+fp8" in variants
            assert "sharded+act-quant-int8" in variants
        # The memplan gate plans the SAME seven variants per model (the
        # traces are shared, not re-traced) against the checked-in
        # baselines.
        memplan = report["gates"]["memplan"]
        assert memplan["ok"], memplan
        assert set(memplan["models"]) == set(harness.SWEEP_MODELS)
        for variants in memplan["models"].values():
            assert len(variants) == len(harness.SWEEP_VARIANTS)
            for row in variants.values():
                assert row["peak_bytes"] > 0
        # The certify gate fingerprints the SAME builds (cached traces):
        # a re-trace must reproduce the digest, a seeded-divergent build
        # must not, and every zoo build gets a digest.
        certify = report["gates"]["certify"]
        assert certify["ok"], certify
        assert certify["stable"] and certify["seeded_divergent"]
        assert set(certify["models"]) == set(harness.SWEEP_MODELS)
        for variants in certify["models"].values():
            assert len(variants) == len(harness.SWEEP_VARIANTS)
            for digest in variants.values():
                assert len(digest) == 64  # sha256 hex

    def test_static_parity_mlp(self, world8):
        from horovod_tpu.analysis import harness

        assert harness.lint_parity("mlp") == ()

    def test_accum_order_parity_mlp(self, world8):
        # accum_steps=1 and K emit identical collective sequences (the
        # static form of comm_audit --microbatch-parity).
        from horovod_tpu.analysis import harness
        from horovod_tpu.parallel import dp

        spec = harness.get_spec("mlp")
        traced = {}
        for k in (1, 4):
            step, opt = dp.make_train_step(
                spec.loss_fn, optax.adam(1e-3), accum_steps=k, lint=False
            )
            state = jax.eval_shape(
                lambda: dp.init_state(spec.make_params(), opt)
            )
            traced[k] = (step._mapped_for(state), (state, spec.batch))
        assert not compare_collectives(*traced[1], *traced[4])


@pytest.mark.slow
class TestCommAuditLint:
    def test_static_fusion_parity_gpt2(self, world8):
        import tools.comm_audit as comm_audit

        row = comm_audit.lint_audit("gpt2_small_16x1024", sharded=True)
        assert row["clean"], row["findings"]
        assert row["parity_ok"]
        # Real bucket structure: >1 predicted bucket at 128 MB over the
        # ~0.5 GB fp32 gradient payload, all matched in the jaxpr.
        assert len(row["predicted_buckets"]) > 1
        kinds = {c["kind"] for c in row["jaxpr_collectives"]}
        assert {"reduce_scatter", "all_gather"} <= kinds
