"""Unified runtime telemetry: registry, exporters, instrumentation, top.

Covers the obs subsystem end to end on the virtual CPU mesh: registry
semantics (env gating, null-registry cost path, histogram percentiles),
JSONL/Prometheus export schemas, the instrumented layers (train step
breakdown, fusion layout gauges, eager collective latency/ops, stall
age gauges, elastic driver events) and the ``hvdtpu_top`` reader. The
cross-process leg (real ``process_count() == 2`` DCN bytes) lives in
``tests/test_multiprocess_dcn.py`` (slow tier).
"""

import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

def cpu_devices(n):
    devs = jax.devices("cpu")
    assert len(devs) >= n
    return devs[:n]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name,
        os.path.join(os.path.dirname(__file__), "..", "tools", f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def metrics_env(tmp_path, monkeypatch):
    """Enable the metrics plane into a scratch dir; clean registry after."""
    from horovod_tpu.obs import export as exp_mod
    from horovod_tpu.obs import registry as reg_mod

    monkeypatch.setenv("HVDTPU_METRICS", "1")
    monkeypatch.setenv("HVDTPU_METRICS_DIR", str(tmp_path))
    monkeypatch.setenv("HVDTPU_METRICS_INTERVAL", "0.01")
    reg_mod._registry.reset()
    reg_mod._enabled = None  # re-read the env on next ask
    monkeypatch.setattr(exp_mod, "_reporter", None)
    yield tmp_path
    reg_mod._registry.reset()
    reg_mod._enabled = None


# ---- peak table ------------------------------------------------------------


@pytest.mark.parametrize(
    "platform,kind,want",
    [
        ("tpu", "TPU v5 lite", 197.0),
        ("tpu", "TPU v4", 275.0),
        ("cpu", "cpu", None),  # NaN: nothing to claim against
        ("tpu", "TPU v9 imaginary", ValueError),
        ("gpu", "NVIDIA H100", ValueError),
    ],
)
def test_peak_tflops_unknown_accelerator_is_an_error(platform, kind, want):
    """Only a cpu device may have no peak; an accelerator whose kind is
    missing from the table raises instead of yielding ``mfu: null``."""
    import types

    from horovod_tpu.obs import flops

    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    if want is ValueError:
        with pytest.raises(ValueError, match="PEAK_TFLOPS_BF16"):
            flops.peak_tflops(dev)
    elif want is None:
        assert np.isnan(flops.peak_tflops(dev))
        assert flops.mfu(1.0, 1.0, device=dev) is None
    else:
        assert flops.peak_tflops(dev) == want


# ---- registry --------------------------------------------------------------


def test_disabled_by_default(monkeypatch):
    from horovod_tpu import obs
    from horovod_tpu.obs import registry as reg_mod

    monkeypatch.delenv("HVDTPU_METRICS", raising=False)
    monkeypatch.setattr(reg_mod, "_enabled", None)
    assert not obs.enabled()
    # Disabled instruments are the shared no-op singleton: recording is
    # free and creates nothing in the real registry.
    c = obs.metrics().counter("never")
    c.inc(5)
    assert c.get() == 0.0
    assert "never" not in reg_mod._registry.snapshot()["counters"]


def test_counter_gauge_histogram(metrics_env):
    from horovod_tpu import obs

    reg = obs.metrics()
    c = reg.counter("c")
    c.inc()
    c.inc(9)
    assert c.get() == 10
    g = reg.gauge("g")
    g.set(2.5)
    g.add(0.5)
    assert g.get() == 3.0
    h = reg.histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["p50"] == 50.0
    assert s["p95"] == 95.0
    assert s["p99"] == 99.0
    assert s["max"] == 100.0
    assert abs(s["mean"] - 50.5) < 1e-9


def test_histogram_ring_bounds_memory(metrics_env):
    from horovod_tpu import obs

    h = obs.metrics().histogram("ring", window=8)
    for v in range(1000):
        h.observe(float(v))
    assert len(h._buf) == 8
    s = h.summary()
    assert s["count"] == 1000  # cumulative count survives the window
    assert s["p50"] >= 992.0  # percentiles reflect the recent window


def test_registry_thread_safety(metrics_env):
    from horovod_tpu import obs

    reg = obs.metrics()

    def work(k):
        for i in range(500):
            reg.counter(f"t.{k}").inc()
            reg.histogram("t.h").observe(i)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert all(snap["counters"][f"t.{k}"] == 500 for k in range(4))
    assert snap["histograms"]["t.h"]["count"] == 2000


# ---- exporters -------------------------------------------------------------


def test_jsonl_and_prom_export(metrics_env):
    from horovod_tpu import obs
    from horovod_tpu.obs.export import MetricsReporter

    reg = obs.metrics()
    reg.counter("exp.c").inc(7)
    reg.gauge("exp.g").set(1.25)
    reg.histogram("exp.h").observe(3.0)
    reg.event("exp.ev", detail="x")
    rep = MetricsReporter(directory=str(metrics_env))
    rec = rep.flush()
    # JSONL: one self-contained object per flush.
    lines = open(rep.jsonl_path()).read().splitlines()
    assert len(lines) == 1
    parsed = json.loads(lines[0])
    assert parsed["counters"]["exp.c"] == 7
    assert parsed["gauges"]["exp.g"] == 1.25
    assert parsed["histograms"]["exp.h"]["count"] == 1
    assert parsed["events"][0]["kind"] == "exp.ev"
    assert {"ts", "rank", "world"} <= set(parsed)
    # Events drain: the next flush must not repeat them.
    rec2 = rep.flush()
    assert rec2["events"] == []
    # Prometheus textfile: typed series, metric names sanitized.
    prom = open(rep.prom_path()).read()
    assert "# TYPE hvdtpu_exp_c counter" in prom
    assert 'hvdtpu_exp_c{rank="0"} 7' in prom
    assert 'hvdtpu_exp_g{rank="0"} 1.25' in prom
    assert 'hvdtpu_exp_h_p50{rank="0"}' in prom
    assert rec["ts"] <= rec2["ts"]


def test_reporter_role_stem(metrics_env):
    from horovod_tpu.obs.export import MetricsReporter

    rep = MetricsReporter(directory=str(metrics_env), role="driver")
    rep.flush()
    assert os.path.exists(os.path.join(str(metrics_env), "driver.jsonl"))
    assert os.path.exists(os.path.join(str(metrics_env), "driver.prom"))


def test_flush_noop_when_disabled(tmp_path, monkeypatch):
    from horovod_tpu.obs import registry as reg_mod
    from horovod_tpu.obs.export import MetricsReporter

    monkeypatch.delenv("HVDTPU_METRICS", raising=False)
    monkeypatch.setattr(reg_mod, "_enabled", None)
    rep = MetricsReporter(directory=str(tmp_path))
    assert rep.flush() is None
    assert list(tmp_path.iterdir()) == []


# ---- instrumented layers ---------------------------------------------------


def test_train_step_breakdown_and_fusion_gauges(metrics_env):
    import horovod_tpu as hvd
    from horovod_tpu import obs
    from horovod_tpu.parallel import dp

    hvd.init(devices=cpu_devices(8))
    try:

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        params = {"w": jnp.ones((4, 2))}
        step, opt = dp.make_train_step(
            loss_fn, optax.sgd(0.01), tokens_per_step=64, flops_per_step=1e6
        )
        state = dp.init_state(params, opt)
        batch = (jnp.ones((8, 4)), jnp.zeros((8, 2)))
        for _ in range(3):
            state, _loss = step(state, batch)
        snap = obs.metrics().snapshot()
        assert snap["counters"]["step.count"] == 3
        assert snap["counters"]["step.tokens"] == 192
        # A stamp closes the step BEFORE the one just dispatched (the
        # wrapper blocks on the previous loss only): three calls book two.
        assert snap["histograms"]["step.total_ms"]["count"] == 2
        assert snap["histograms"]["step.host_dispatch_ms"]["count"] == 2
        assert snap["histograms"]["step.device_ms"]["count"] == 2
        assert snap["gauges"]["step.tokens_per_sec"] > 0
        # Fusion layout gauges pin the per-step collective payload: the
        # gradient tree is one fp32 bucket of 4*2 elements = 32 bytes.
        assert snap["gauges"]["fusion.allreduce.bytes_per_step"] == 32.0
        assert snap["gauges"]["fusion.allreduce.buckets"] == 1.0
        assert snap["gauges"]["optimizer.grad_bytes_per_step"] == 32.0
        # The reporter ticked: at least one JSONL flush landed.
        files = [f for f in os.listdir(str(metrics_env)) if f.endswith(".jsonl")]
        assert files
    finally:
        hvd.shutdown()


def test_enable_after_step_built(tmp_path, monkeypatch):
    """obs.enable() must take effect on an already-built train step: the
    wrapper checks enablement per call, not per build."""
    import horovod_tpu as hvd
    from horovod_tpu import obs
    from horovod_tpu.obs import export as exp_mod
    from horovod_tpu.obs import registry as reg_mod
    from horovod_tpu.parallel import dp

    monkeypatch.delenv("HVDTPU_METRICS", raising=False)
    monkeypatch.setenv("HVDTPU_METRICS_DIR", str(tmp_path))
    reg_mod._registry.reset()
    reg_mod._enabled = None
    monkeypatch.setattr(exp_mod, "_reporter", None)
    hvd.init(devices=cpu_devices(8))
    try:

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        step, opt = dp.make_train_step(loss_fn, optax.sgd(0.01))
        state = dp.init_state({"w": jnp.ones((4, 2))}, opt)
        batch = (jnp.ones((8, 4)), jnp.zeros((8, 2)))
        state, _ = step(state, batch)  # disabled: nothing recorded
        assert obs.metrics().snapshot()["counters"] == {}
        obs.enable()
        state, _ = step(state, batch)
        assert obs.metrics().snapshot()["counters"]["step.count"] == 1
        obs.disable()
        state, _ = step(state, batch)
        # metrics() now routes to the null registry; the real one must
        # not have advanced while disabled.
        assert reg_mod._registry.snapshot()["counters"]["step.count"] == 1
    finally:
        hvd.shutdown()
        reg_mod._registry.reset()
        reg_mod._enabled = None


def test_empty_histogram_exports_strict_json(metrics_env):
    """A created-but-never-observed histogram must not poison the JSONL
    with bare NaN literals (strict parsers reject them)."""
    from horovod_tpu import obs

    obs.metrics().histogram("never.observed")
    rec = obs.flush()
    assert rec["histograms"]["never.observed"]["count"] == 0
    assert rec["histograms"]["never.observed"]["p50"] is None
    from horovod_tpu.obs.export import reporter

    text = open(reporter().jsonl_path()).read()
    assert "NaN" not in text  # json.dumps would spell a float nan this way
    json.loads(text.splitlines()[-1])  # round-trips
    # The prom textfile spells the empty fields NaN, which IS the
    # Prometheus text-format literal for an unknown sample.
    prom = open(reporter().prom_path()).read()
    assert 'hvdtpu_never_observed_p50{rank="0"} NaN' in prom


def test_pack_unpack_timed(metrics_env):
    from horovod_tpu import obs
    from horovod_tpu.ops import fusion

    bufs, spec = fusion.pack({"a": jnp.ones((8,)), "b": jnp.ones((3,))})
    fusion.unpack(bufs, spec)
    snap = obs.metrics().snapshot()
    assert snap["histograms"]["fusion.pack_ms"]["count"] == 1
    assert snap["histograms"]["fusion.unpack_ms"]["count"] == 1


def test_eager_collective_metrics(metrics_env):
    from horovod_tpu import obs
    from horovod_tpu.ops import eager
    from horovod_tpu.ops.collectives import Sum

    out = eager.allreduce(np.ones((4,), np.float32), Sum)
    np.testing.assert_allclose(np.asarray(out), np.ones((4,)))
    snap = obs.metrics().snapshot()
    assert snap["counters"]["eager.ops"] == 1
    assert snap["histograms"]["eager.EAGER_ALLREDUCE.ms"]["count"] == 1


def test_stall_age_gauges(metrics_env):
    from horovod_tpu import obs
    from horovod_tpu.utils.stall import StallInspector

    insp = StallInspector(warning_time=0.01, shutdown_time=0.0)
    insp.record_uncached_tensor("grad_0", rank=0)
    time.sleep(0.03)
    stalled = insp.check(world_size=2)
    assert stalled == ["grad_0"]
    snap = obs.metrics().snapshot()
    assert snap["gauges"]["stall.pending"] == 1.0
    assert snap["gauges"]["stall.max_age_s"] > 0
    assert snap["gauges"]["stall.age_s.grad_0"] > 0
    # Completion REMOVES the per-tensor gauge (labels are unique per op,
    # so retired gauges would otherwise grow the registry forever).
    insp.remove_tensor("grad_0")
    insp.check(world_size=2)
    snap = obs.metrics().snapshot()
    assert "stall.age_s.grad_0" not in snap["gauges"]
    assert snap["gauges"]["stall.pending"] == 0.0


def test_stall_warns_once_single_locked_pass(metrics_env, caplog):
    import logging

    from horovod_tpu.utils.stall import StallInspector

    insp = StallInspector(warning_time=0.01)
    insp.record_uncached_tensor("t", rank=0)
    time.sleep(0.02)
    with caplog.at_level(logging.WARNING, logger="horovod_tpu.stall"):
        insp.check(world_size=2)
        insp.check(world_size=2)  # second scan: already warned, no repeat
    warnings = [r for r in caplog.records if "not yet joined" in r.message]
    assert len(warnings) == 1


def test_elastic_blacklist_event(metrics_env, monkeypatch):
    from horovod_tpu import obs
    from horovod_tpu.runner import elastic_driver
    from horovod_tpu.runner.elastic_driver import FixedHosts, HostManager

    # Fresh driver reporter so it picks up this test's metrics dir.
    monkeypatch.setattr(elastic_driver, "_driver_rep", None)
    hm = HostManager(FixedHosts({"a": 1, "b": 1}))
    hm.update_available_hosts()
    hm.blacklist("b")
    assert hm.current_hosts == {"a": 1}
    snap = obs.metrics().snapshot()
    assert snap["counters"]["elastic.blacklist_events"] == 1
    assert snap["gauges"]["elastic.blacklisted_hosts"] == 1.0
    # Blacklists flush the driver reporter immediately (the next rescale
    # may never come): the event is durable in driver.jsonl, and the
    # in-memory ring is already drained.
    rec = json.loads(
        open(os.path.join(str(metrics_env), "driver.jsonl")).read()
        .splitlines()[-1]
    )
    assert any(
        e["kind"] == "elastic.blacklist" and e["host"] == "b"
        for e in rec["events"]
    )
    assert obs.metrics().drain_events() == []


def test_native_bridge_passive_without_lib():
    # Must never trigger a native build: with the lib unloaded the bridge
    # reports nothing (the pure-SPMD path pays zero for it).
    import horovod_tpu.native as native
    from horovod_tpu.obs.native_bridge import read_native

    if native._lib is not None:
        pytest.skip("native lib already loaded in this process")
    assert read_native() == {}


# ---- timeline stop drain (satellite fix) -----------------------------------


def test_timeline_stop_drains_queue(tmp_path):
    from horovod_tpu.utils.timeline import Timeline

    path = str(tmp_path / "tl.json")
    tl = Timeline(path)
    tl.start(path)
    n = 500
    for i in range(n):
        tl.instant("tensor", f"ev{i}")
    tl.stop()
    # Every queued record was written before close, and the file is a
    # complete, parseable chrome-trace array.
    data = json.loads(open(path).read())
    names = {r.get("name") for r in data}
    assert {f"ev{i}" for i in range(n)} <= names
    # Idempotent stop.
    tl.stop()


def test_timeline_stop_without_start():
    from horovod_tpu.utils.timeline import Timeline

    Timeline().stop()  # no file, no thread: plain no-op


# ---- hvdtpu_top ------------------------------------------------------------


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_hvdtpu_top_rates_and_render(tmp_path):
    top = _load_tool("hvdtpu_top")
    base = {
        "world": 2,
        "gauges": {"step.mfu": 0.42, "stall.pending": 0.0,
                   "fusion.allreduce.bytes_per_step": 1048576.0},
        "histograms": {"step.total_ms": {"p50": 100.0, "p95": 120.0},
                       "step.host_dispatch_ms": {"p50": 2.0}},
        "events": [],
    }
    for rank in (0, 1):
        _write_jsonl(
            tmp_path / f"rank{rank}.jsonl",
            [
                {**base, "ts": 1000.0, "rank": rank,
                 "counters": {"step.count": 10, "step.tokens": 1000,
                              "eager.bytes": 0,
                              "native.cache_hits": 90,
                              "native.cache_misses": 10}},
                {**base, "ts": 1010.0, "rank": rank,
                 "counters": {"step.count": 110, "step.tokens": 11000,
                              "eager.bytes": 4096,
                              "native.cache_hits": 190,
                              "native.cache_misses": 10},
                 "events": [{"ts": 1009.0, "kind": "elastic.rescale",
                             "round": 1}]},
            ],
        )
    rows, events = top.collect(str(tmp_path))
    assert len(rows) == 2
    r0 = rows[0]
    assert r0["who"] == "rank0"
    assert r0["steps"] == 110
    assert r0["steps_s"] == pytest.approx(10.0)
    assert r0["tok_s"] == pytest.approx(1000.0)
    assert r0["mfu"] == 0.42
    assert r0["cache"] == pytest.approx(0.95)
    assert r0["eager_bs"] == pytest.approx(409.6)
    assert len(events) == 2
    out = top.render(rows, events, str(tmp_path))
    assert "rank0" in out and "rank1" in out
    assert "elastic.rescale" in out
    assert "0.420" in out
    # --once exit path
    assert top.main(["--dir", str(tmp_path), "--once"]) == 0
    assert top.main(["--dir", str(tmp_path / "empty"), "--once"]) == 1


def test_hvdtpu_top_tail_torn_line(tmp_path):
    top = _load_tool("hvdtpu_top")
    p = tmp_path / "rank0.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"ts": 1.0, "counters": {}, "gauges": {},
                            "histograms": {}}) + "\n")
        f.write('{"ts": 2.0, "counters": {"x"')  # mid-write tear
    recs = top._tail_records(str(p))
    assert len(recs) == 1 and recs[0]["ts"] == 1.0


# ---- env lint (satellite: tools/check_env_vars.py) -------------------------


def test_env_vars_all_declared():
    checker = _load_tool("check_env_vars")
    bad = checker.check()
    assert not bad, (
        "undeclared HVDTPU_* env vars (declare in horovod_tpu/utils/env.py "
        f"or csrc/env_parser.cc): {bad}"
    )


def test_env_lint_catches_undeclared(tmp_path, monkeypatch):
    checker = _load_tool("check_env_vars")
    # A reference to a var nobody declared must be reported. The fake
    # name is assembled at runtime so the lint's own scan of this test
    # file never sees the literal.
    fake = "HVDTPU_" + "TOTALLY_NOT_A_KNOB"
    refs = checker.referenced()
    refs.setdefault(fake, []).append("fake.py:1")
    monkeypatch.setattr(checker, "referenced", lambda: refs)
    bad = checker.check()
    assert any(tok == fake for tok, _ in bad)
