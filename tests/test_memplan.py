"""Static HBM memory planner (``horovod_tpu.analysis.memory``).

Four contracts, mirroring the linter's test shape (each rule fires on a
seeded-broken step; the honest models hold):

* **measured**: the planner's resident-bytes accounting matches what a
  real step actually leaves allocated on a CPU host
  (``jax.live_arrays``) within the declared tolerance, for mlp and
  bert-tiny — the drift gate (``memory.compare_to_measured``);
* **models**: donation on/off, remat ``full < dots_saveable < none``
  activation ordering, ZeRO-1 ~1/N opt-state at world 4 and 8;
* **rules**: ``oom-risk`` / ``donation-missed-reuse`` /
  ``peak-regression`` each fire on a seeded-broken build and respect
  the allowlist;
* **baselines**: the checked-in ``tools/memplan_baselines.json``
  round-trips through the ``run_lints`` memplan gate.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.analysis import (
    MemoryLintConfig,
    apply_allowlist,
    harness,
    plan_traced,
)
from horovod_tpu.analysis import memory as _mem
from horovod_tpu.analysis import rules as _rules
from horovod_tpu.parallel import dp
from horovod_tpu.utils import env as _env


def _mlp_concrete():
    from horovod_tpu.models import MLP

    model = MLP(features=(64,))

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply({"params": params}, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        ).mean()

    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 784)))["params"]
    batch = (
        jnp.zeros((32, 784), jnp.float32),
        jnp.zeros((32,), jnp.int32),
    )
    return loss_fn, params, batch


def _gpt2_spec(n_layers=4, max_len=256, seq=128, batch=64, remat=False):
    """Per-block remat variant of the zoo gpt2 (the model-config knob —
    the surface whose residual choice the planner must price)."""
    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel

    cfg = GPT2Config.tiny(n_layers=n_layers, max_len=max_len, remat=remat)
    model = GPT2LMModel(cfg)

    def make_params():
        return model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, seq), jnp.int32)
        )["params"]

    def loss_fn(params, tokens):
        logits = model.apply({"params": params}, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tokens[:, 1:]
        ).mean()

    return loss_fn, make_params, jax.ShapeDtypeStruct(
        (batch, seq + 1), jnp.int32
    )


def _abstract_plan(step, opt, make_params, batch, **kw):
    state = jax.eval_shape(lambda: dp.init_state(make_params(), opt))
    return step.memplan(state, batch)


class TestMeasured:
    """Prediction vs a real step's allocation on the CPU host."""

    @staticmethod
    def _gate(loss_fn, params, batch):
        """Plan the build, run ONE real step, gate the plan against the
        live-bytes delta (old state donated away, new state + loss
        appear) plus the still-live batch: the resident footprint the
        plan's outer avals predict. Consumes ``params`` (donated)."""
        step, opt = dp.make_train_step(loss_fn, optax.adamw(1e-4), lint=False)
        state = dp.init_state(params, opt)
        plan = step.memplan(state, batch)
        before = _mem.snapshot_live_ids()
        out = step(state, batch)
        jax.block_until_ready(out)
        measured = _mem.live_array_bytes(exclude_ids=before) + sum(
            int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(batch)
        )
        return _mem.compare_to_measured(plan, measured, "live_arrays")

    @pytest.mark.parametrize("name", ["mlp", "bert"])
    def test_resident_within_tolerance(self, world8, name):
        spec = harness.get_spec(name)
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(spec.make_params),
        )
        batch = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), spec.batch
        )
        rec = self._gate(spec.loss_fn, params, batch)
        assert rec["ok"], rec

    def test_gate_record_on_concrete_mlp(self, world8):
        """What the gate returns for a concrete (not zoo) model: the
        verdict, its source and the plan's breakdown by category."""
        rec = self._gate(*_mlp_concrete())
        assert rec["ok"] is True, rec
        assert rec["source"] == "live_arrays"
        assert rec["predicted_peak_bytes"] >= rec["predicted_resident_bytes"] // 2
        assert set(rec["breakdown"]) == set(_mem.CATEGORIES)

    def test_compare_semantics(self):
        plan = _mem.MemoryPlan(
            peak_bytes=1000,
            breakdown={},
            resident_bytes=700,
            global_state_bytes=800,
            params_bytes=0,
            opt_state_bytes=0,
            batch_bytes=0,
            wire_bytes=0,
            activation_bytes=0,
            donation_saved_bytes=0,
            undonated_candidates=(),
            world=8,
            n_eqns=0,
            n_buffers=0,
        )
        # live_arrays compares resident, two-sided.
        assert _mem.compare_to_measured(plan, 800, "live_arrays")["ok"]
        assert not _mem.compare_to_measured(plan, 80, "live_arrays")["ok"]
        # device_peak: the model is an upper bound on the compiled
        # schedule — only under-prediction fails.
        assert _mem.compare_to_measured(plan, 900, "device_peak")["ok"]
        assert not _mem.compare_to_measured(plan, 5000, "device_peak")["ok"]
        # A stale lifetime peak (no new high-water mark during the
        # measured step) yields no verdict, not a spurious failure.
        assert (
            _mem.compare_to_measured(plan, 5000, "device_peak_stale")["ok"]
            is None
        )


class TestModel:
    """The deltas the planner exists to price."""

    def test_donation_cuts_peak(self, world8):
        spec = harness.get_spec("mlp")
        step, opt = dp.make_train_step(
            spec.loss_fn, optax.adamw(1e-4), lint=False
        )
        state = jax.eval_shape(lambda: dp.init_state(spec.make_params(), opt))
        fn = step._mapped_for(state)
        don = plan_traced(
            fn, (state, spec.batch), donate_argnums=(0,), world=8
        )
        nodon = plan_traced(fn, (state, spec.batch), world=8)
        assert don.peak_bytes < nodon.peak_bytes
        assert don.donation_saved_bytes > 0
        # The undonated build names the missed aliases; the donated one
        # has none left.
        assert nodon.undonated_candidates
        assert not don.undonated_candidates

    def test_remat_activation_ordering(self, world8):
        """Per-block remat on a 4-layer gpt2 with activation-dominated
        shapes: full < dots_saveable < none, both in activation bytes
        and peak."""
        peaks, acts = {}, {}
        for remat in ("none", "full", "dots_saveable"):
            loss_fn, make_params, batch = _gpt2_spec(
                remat=False if remat == "none" else remat
            )
            step, opt = dp.make_train_step(
                loss_fn, optax.adamw(1e-4), lint=False
            )
            plan = _abstract_plan(step, opt, make_params, batch)
            peaks[remat], acts[remat] = plan.peak_bytes, plan.activation_bytes
        assert acts["full"] < acts["dots_saveable"] < acts["none"], acts
        assert peaks["full"] < peaks["dots_saveable"] < peaks["none"], peaks

    @pytest.mark.parametrize("world", [4, 8])
    def test_zero1_opt_state_is_1_over_n(self, world):
        # Own world per case: the ZeRO-1 pad/shard factor is the
        # CONTEXT world size, so world 4 needs a 4-device init (a
        # mesh= override alone would disagree with the optimizer pad).
        hvd.init(devices=jax.devices("cpu")[:world])
        try:
            spec = harness.get_spec("mlp")
            plans = {}
            for sharded in (False, True):
                step, opt = dp.make_train_step(
                    spec.loss_fn,
                    optax.adamw(1e-4),
                    sharded=sharded,
                    lint=False,
                )
                plans[sharded] = _abstract_plan(
                    step, opt, spec.make_params, spec.batch
                )
            full = plans[False].opt_state_bytes
            shard = plans[True].opt_state_bytes
            # mu+nu shard 1/N (count stays replicated); padding slack.
            assert shard == pytest.approx(full / world, rel=0.15), (
                full,
                shard,
                world,
            )
            assert plans[True].peak_bytes < plans[False].peak_bytes
        finally:
            hvd.shutdown()

    def test_accum_steps_peels_microbatch(self, world8):
        """accum_steps=K slices the batch: the per-microbatch
        activation footprint shrinks vs K=1 on batch-heavy shapes."""
        loss_fn, make_params, batch = _gpt2_spec(n_layers=2)
        plans = {}
        for k in (1, 4):
            step, opt = dp.make_train_step(
                loss_fn, optax.adamw(1e-4), accum_steps=k, lint=False
            )
            plans[k] = _abstract_plan(step, opt, make_params, batch)
        assert plans[4].peak_bytes < plans[1].peak_bytes

    def test_projection_ladder(self, world8):
        plan = harness.memplan_model("mlp", sharded=True)
        proj = _mem.project_sharding(plan)
        assert (
            proj["zero3_peak_bytes"]
            < proj["zero2_peak_bytes"]
            < proj["zero1_peak_bytes"]
        )

    def test_wire_bytes_quantized_vs_sharded(self, world8):
        """The sharded build materializes packed flat buckets (wire
        category nonzero); the planner sees them."""
        plan = harness.memplan_model("mlp", sharded=True)
        assert plan.wire_bytes > 0
        assert sum(plan.breakdown.values()) == plan.peak_bytes


class TestRulesFire:
    """Each memory rule on a seeded-broken build, plus allowlisting."""

    def _mlp_step(self, world8, **kw):
        spec = harness.get_spec("mlp")
        step, opt = dp.make_train_step(
            spec.loss_fn, optax.adamw(1e-4), lint=False, **kw
        )
        state = jax.eval_shape(lambda: dp.init_state(spec.make_params(), opt))
        return step, state, spec.batch

    def test_oom_risk_fires_and_allowlists(self, world8):
        step, state, batch = self._mlp_step(world8)
        f = step.lint(
            state, batch, memory=MemoryLintConfig(budget_bytes=1024)
        )
        assert [x.rule for x in f] == ["oom-risk"]
        assert "exceeds the declared HBM budget" in f[0].message
        assert not apply_allowlist(f, ("oom-risk",))
        # A generous budget stays silent.
        assert not step.lint(
            state, batch, memory=MemoryLintConfig(budget_bytes=1 << 40)
        )

    def test_oom_risk_env_budget(self, world8, monkeypatch):
        monkeypatch.setenv("HVDTPU_HBM_BUDGET_GB", "0.000001")
        step, state, batch = self._mlp_step(world8)
        f = step.lint(state, batch)
        assert "oom-risk" in [x.rule for x in f]
        monkeypatch.setenv("HVDTPU_HBM_BUDGET_GB", "-1")
        with pytest.raises(ValueError):
            _env.hbm_budget_bytes()

    def test_donation_missed_reuse_fires(self, world8):
        step, state, batch = self._mlp_step(world8, donate=False)
        f = step.lint(state, batch, memory=MemoryLintConfig())
        rules = [x.rule for x in f]
        assert "donation-missed-reuse" in rules
        missed = [x for x in f if x.rule == "donation-missed-reuse"]
        assert all(
            x.details["saving_bytes"] > 0.05 * 1 for x in missed
        )
        # ...and the properly-donating build is clean.
        step2, state2, batch2 = self._mlp_step(world8)
        assert not step2.lint(state2, batch2, memory=MemoryLintConfig())

    def test_peak_regression_fires(self, world8):
        plan = harness.memplan_model("mlp")
        good = _rules.rule_memory(
            plan, baseline_bytes=plan.peak_bytes, baseline_key="mlp/replicated"
        )
        assert not good
        bad = _rules.rule_memory(
            plan,
            baseline_bytes=plan.peak_bytes // 2,
            baseline_key="mlp/replicated",
        )
        assert [x.rule for x in bad] == ["peak-regression"]
        assert "mlp/replicated" in bad[0].message
        # Within the +5% tolerance band: silent.
        assert not _rules.rule_memory(
            plan, baseline_bytes=int(plan.peak_bytes / 1.04)
        )


class TestBaselines:
    """tools/memplan_baselines.json round-trip through the gate."""

    def test_checked_in_baselines_cover_the_zoo(self):
        with open("tools/memplan_baselines.json") as f:
            doc = json.load(f)
        assert doc["size"] == "tiny" and doc["world"] == 8
        keys = set(doc["peaks"])
        for m in harness.SWEEP_MODELS:
            for var in harness.SWEEP_VARIANTS:
                assert f"{m}/{harness.variant_label(var)}" in keys

    def test_round_trip_and_seeded_regression(self, world8):
        with open("tools/memplan_baselines.json") as f:
            peaks = json.load(f)["peaks"]
        rows = harness.memplan_sweep(models=("mlp",), baselines=peaks)
        for label, row in rows["mlp"].items():
            assert row["findings"] == (), (label, row["findings"])
        # Seed a regression: halve one baseline.
        broken = dict(peaks)
        broken["mlp/replicated"] = peaks["mlp/replicated"] // 2
        rows = harness.memplan_sweep(models=("mlp",), baselines=broken)
        fired = [
            f.rule
            for row in rows["mlp"].values()
            for f in row["findings"]
        ]
        assert fired == ["peak-regression"]
        # A missing key is itself a finding (the file cannot rot).
        del broken["mlp/replicated"]
        broken["mlp/replicated"] = None
        rows = harness.memplan_sweep(
            models=("mlp",),
            baselines={
                k: v
                for k, v in peaks.items()
                if k != "mlp/replicated"
            },
        )
        fired = [
            f
            for row in rows["mlp"].values()
            for f in row["findings"]
        ]
        assert len(fired) == 1 and "no checked-in peak baseline" in fired[0].message


class TestKnobs:
    def test_memplan_tolerance_validation(self, monkeypatch):
        assert _env.memplan_tolerance() == _env.DEFAULT_MEMPLAN_TOLERANCE
        monkeypatch.setenv("HVDTPU_MEMPLAN_TOLERANCE", "0.5")
        assert _env.memplan_tolerance() == 0.5
        monkeypatch.setenv("HVDTPU_MEMPLAN_TOLERANCE", "1.5")
        with pytest.raises(ValueError):
            _env.memplan_tolerance()

    def test_trace_cache_respects_env_knobs(self, world8, monkeypatch):
        """A cached build/trace must not outlive the env it was built
        under: re-linting after an HVDTPU_FUSION_THRESHOLD change must
        re-trace (a stale trace's collective groups would no longer
        match the freshly-predicted buckets → spurious fusion-parity)."""
        assert harness.lint_model("mlp") == ()
        monkeypatch.setenv("HVDTPU_FUSION_THRESHOLD", "4096")
        assert harness.lint_model("mlp") == ()

    def test_gauge_published(self, world8):
        from horovod_tpu.obs import registry as _obs

        _obs.enable()
        try:
            plan = harness.memplan_model("mlp")
            assert (
                _obs.metrics().gauge("memplan.peak_bytes").get()
                == plan.peak_bytes
            )
        finally:
            _obs.disable()
