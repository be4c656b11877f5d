"""Unified tracing plane + flight recorder (``horovod_tpu.obs.trace``,
``tools/hvdtpu_trace.py``): no-op-when-off guarantees, ring-buffer
eviction order, open-span dumps, Perfetto schema validity, cross-rank
merge under injected clock skew, the atomic Prometheus publish, the
metric-name lint, and the end-to-end seeded-hang evidence chain
(chaos injection + victim's open step span + driver lease-expiry span
on one clock-aligned timeline).
"""

import importlib.util
import json
import os
import sys
import threading
import time

import pytest


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name,
        os.path.join(os.path.dirname(__file__), "..", "tools", f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def trace_env(tmp_path):
    """Arm the tracing plane into a scratch dir; disarm after."""
    from horovod_tpu.obs import trace

    trace._reset_for_tests()
    rec = trace.enable(directory=str(tmp_path), capacity=64)
    yield trace, rec, tmp_path
    trace._reset_for_tests()


# ---- off-path guarantees -------------------------------------------------


def test_disabled_by_default_and_truly_noop(monkeypatch):
    """With HVDTPU_TRACE unset, no site touches the ring: span() opens
    only its profiler-side sink (a ``jax.profiler.TraceAnnotation``,
    inert without a profiler session; the shared null context manager in
    a process that never imported JAX) and the recorder object is never
    even constructed — the strongest form of the trace-off overhead
    guard (no ring, nothing to pay)."""
    import jax.profiler

    from horovod_tpu.obs import trace

    monkeypatch.delenv("HVDTPU_TRACE", raising=False)
    trace._reset_for_tests()
    try:
        assert not trace.enabled()
        s1 = trace.span("a", "train", step=1)
        s2 = trace.span("b", "serve")
        assert isinstance(s1, jax.profiler.TraceAnnotation)
        assert isinstance(s2, jax.profiler.TraceAnnotation)
        with s1:
            pass
        with monkeypatch.context() as m:
            m.setattr(trace, "_TraceAnnotation", None)
            m.delitem(sys.modules, "jax")
            assert trace.span("c", "driver") is trace._NULL_SPAN
        trace.instant("x", cat="chaos", args={"k": 1})
        trace.complete("y", "train", time.time(), 0.01)
        trace.clock_sync(123.0)
        assert trace.flight_dump("nope") is None
        assert trace._recorder is None  # never constructed
    finally:
        trace._reset_for_tests()


def test_env_arming(monkeypatch, tmp_path):
    from horovod_tpu.obs import trace

    monkeypatch.setenv("HVDTPU_TRACE", "1")
    monkeypatch.setenv("HVDTPU_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("HVDTPU_TRACE_BUFFER", "32")
    trace._reset_for_tests()
    try:
        assert trace.enabled()
        with trace.span("s", "train"):
            pass
        assert trace.recorder().capacity == 32
        path = trace.flight_dump("env")
        assert path is not None and path.startswith(str(tmp_path))
    finally:
        trace._reset_for_tests()


# ---- ring semantics ------------------------------------------------------


def test_ring_eviction_order(tmp_path):
    """Oldest events are evicted first; the dump holds exactly the last
    N in recording order."""
    from horovod_tpu.obs import trace

    trace._reset_for_tests()
    try:
        trace.enable(directory=str(tmp_path), capacity=8)
        for i in range(12):
            trace.instant(f"ev{i}", cat="app")
        path = trace.flight_dump("evict")
        events = json.load(open(path))["traceEvents"]
        names = [e["name"] for e in events if e["name"].startswith("ev")]
        assert names == [f"ev{i}" for i in range(4, 12)]
    finally:
        trace._reset_for_tests()


def test_open_span_dumped_as_begin_event(trace_env):
    """A span still open at dump time ships as a ``B`` event (the 'who
    was where' half of a hang dump); once exited it retires to one
    ``X`` complete event with a duration."""
    trace, rec, tmp_path = trace_env
    span = trace.span("worker.step", cat="elastic", step=3)
    span.__enter__()
    path = trace.flight_dump("mid_hang")
    events = json.load(open(path))["traceEvents"]
    open_spans = [
        e for e in events if e["ph"] == "B" and e["name"] == "worker.step"
    ]
    assert len(open_spans) == 1
    assert open_spans[0]["args"]["step"] == 3
    span.__exit__(None, None, None)
    path = trace.flight_dump("after")
    events = json.load(open(path))["traceEvents"]
    assert not [e for e in events if e["ph"] == "B"]
    done = [
        e for e in events if e["ph"] == "X" and e["name"] == "worker.step"
    ]
    assert len(done) == 1 and done[0]["dur"] >= 0


def test_dump_schema_valid_and_reasons_accumulate(trace_env):
    trace, rec, tmp_path = trace_env
    ht = _load_tool("hvdtpu_trace")
    with trace.span("step", "train", step=1):
        trace.instant("guard.skip", cat="guard")
    trace.clock_sync(1000.0, round=2)
    trace.complete("lease.expiry", "elastic", time.time() - 1.0, 1.0,
                   args={"host": "h"})
    p1 = trace.flight_dump("first")
    p2 = trace.flight_dump("second")
    assert p1 == p2  # same stem, latest dump wins
    doc = json.load(open(p2))
    assert ht.validate_events(doc["traceEvents"]) == []
    assert doc["metadata"]["reasons"] == ["first", "second"]
    assert doc["displayTimeUnit"] == "ms"


def test_recorder_thread_safety(trace_env):
    """Concurrent spans from many threads: no exception, every thread's
    events land, open-span books stay consistent."""
    trace, rec, tmp_path = trace_env

    def worker(k):
        # 8 threads x 8 spans = 64 events: exactly the ring capacity,
        # so every thread's records survive for the assertion below.
        for i in range(8):
            with trace.span(f"t{k}", cat="app", i=i):
                pass

    threads = [
        threading.Thread(target=worker, args=(k,)) for k in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.open_spans() == []
    names = {e["name"] for e in rec._ring}
    assert {f"t{k}" for k in range(8)} <= names


# ---- merge + clock alignment --------------------------------------------


def _us(seconds):
    return int(seconds * 1e6)


def _rank_doc(stem, skew_s, sync_delays_s, steps, jitter_s=0.0):
    """A synthetic rank trace whose clock runs ``skew_s`` ahead of the
    driver's: clock_sync observations carry the true driver ts, local
    stamps add skew + a positive KV delay; step spans start at
    driver-time 1000+k (+ jitter)."""
    events = []
    for k, delay in enumerate(sync_delays_s):
        driver_ts = 990.0 + k
        events.append({
            "ph": "i", "name": "clock_sync", "cat": "clock", "s": "t",
            "ts": _us(driver_ts + skew_s + delay), "tid": 1,
            "args": {"driver_ts": driver_ts, "round": k},
        })
    for k in steps:
        events.append({
            "ph": "X", "name": "worker.step", "cat": "elastic",
            "ts": _us(1000.0 + k + skew_s + jitter_s), "dur": _us(0.5),
            "tid": 1, "args": {"step": k},
        })
    return {"traceEvents": events, "metadata": {"stem": stem}}


def test_merge_recovers_injected_clock_skew():
    """Two ranks with ±seconds of injected skew: the merge recovers
    each offset within the smallest injected KV delay, and the (round,
    step) correlation lines align — cross-rank step skew collapses
    from seconds to the jitter actually injected."""
    ht = _load_tool("hvdtpu_trace")
    driver = {
        "traceEvents": [
            {"ph": "X", "name": "round.publish", "cat": "elastic",
             "ts": _us(990.0), "dur": _us(0.01), "tid": 1,
             "args": {"round": 0}},
        ],
        "metadata": {"stem": "driver", "role": "driver"},
    }
    skew_a, skew_b = 3.7, -1.2
    rank_a = _rank_doc("hostA", skew_a, [0.005, 0.020, 0.015], [1, 2, 3])
    rank_b = _rank_doc(
        "hostB", skew_b, [0.008, 0.006, 0.030], [1, 2, 3],
        jitter_s=0.004,
    )
    merged = ht.merge([driver, rank_a, rank_b])
    offs = merged["metadata"]["clock_offsets_us"]
    assert offs["driver"] is None  # the reference clock itself
    assert abs(offs["hostA"] - _us(skew_a)) <= _us(0.006)
    assert abs(offs["hostB"] - _us(skew_b)) <= _us(0.007)
    rep = ht.report(merged)
    # Unaligned, steps would differ by |skew_a - skew_b| ≈ 4.9 s;
    # aligned, only the injected 4 ms jitter (+ delay floor) remains.
    assert rep["max_step_skew_ms"] <= 15.0
    # Correlation lines: a global marker per round and per step.
    markers = {
        e["name"] for e in merged["traceEvents"]
        if e.get("cat") == "correlation"
    }
    assert {"round 0", "step 1", "step 2", "step 3"} <= markers
    assert ht.validate_events(merged["traceEvents"]) == []


def test_report_phase_percentiles():
    ht = _load_tool("hvdtpu_trace")
    events = [
        {"ph": "X", "name": "step", "cat": "train", "ts": _us(i),
         "dur": _us(0.001 * (i + 1)), "tid": 1, "args": {"step": i}}
        for i in range(10)
    ]
    rep = ht.report(ht.merge([
        {"traceEvents": events, "metadata": {"stem": "r0"}}
    ]))
    row = rep["phases"]["train:step"]
    assert row["count"] == 10
    assert row["p50_ms"] <= row["p95_ms"] <= row["max_ms"] == 10.0


def test_merge_dir_and_cli_roundtrip(trace_env):
    trace, rec, tmp_path = trace_env
    with trace.span("step", "train", step=1):
        pass
    trace.flight_dump("t")
    ht = _load_tool("hvdtpu_trace")
    out = os.path.join(str(tmp_path), "merged.json")
    merged = ht.merge_dir(str(tmp_path), out=out)
    assert merged is not None and os.path.exists(out)
    assert ht.validate_events(json.load(open(out))["traceEvents"]) == []
    assert ht.merge_dir(os.path.join(str(tmp_path), "empty")) is None


# ---- native timeline bridge ---------------------------------------------


def test_timeline_mirrors_into_trace_ring(trace_env, tmp_path):
    """With both planes armed, host-timeline activities land in the
    span ring under cat='native' — one dump, both planes — and the
    timeline file itself carries the trace_epoch rebase metadata."""
    trace, rec, _ = trace_env
    from horovod_tpu.utils.timeline import Timeline

    path = os.path.join(str(tmp_path), "tl.json")
    tl = Timeline(path)
    tl.start()
    with tl.activity("grad_0", "NEGOTIATE_ALLREDUCE"):
        pass
    tl.instant("grad_0", "CYCLE")
    tl.stop()
    native = [e for e in rec._ring if e.get("cat") == "native"]
    phs = [e["ph"] for e in native]
    assert "B" in phs and "E" in phs and "i" in phs
    assert all(e["args"]["tensor"] == "grad_0" for e in native)
    # The file's epoch metadata lets hvdtpu_trace rebase it.
    ht = _load_tool("hvdtpu_trace")
    doc = ht.load_trace(path)
    assert doc["metadata"].get("rebased_from_epoch")
    merged = ht.merge([doc])
    assert ht.validate_events(merged["traceEvents"]) == []


# ---- flight-dump trigger sites ------------------------------------------


def test_guard_escalation_dumps(trace_env):
    trace, rec, tmp_path = trace_env
    from horovod_tpu.obs import guard as obs_guard

    obs_guard.record_escalation(5)
    path = os.path.join(
        str(tmp_path), os.path.basename(trace.flight_dump("probe"))
    )
    doc = json.load(open(path))
    assert "guard_escalation" in doc["metadata"]["reasons"]
    names = {e["name"] for e in doc["traceEvents"]}
    assert "guard.escalation" in names


def test_stall_shutdown_breach_dumps(trace_env, monkeypatch):
    trace, rec, tmp_path = trace_env
    from horovod_tpu.utils.stall import StallInspector

    killed = []
    insp = StallInspector(
        warning_time=0.0, shutdown_time=0.01, on_shutdown=killed.append
    )
    insp.record_uncached_tensor("wedged", rank=0)
    time.sleep(0.05)
    insp.check(world_size=2)
    assert killed
    assert "stall_shutdown" in rec.dump_reasons
    assert any(e["name"] == "stall.shutdown" for e in rec._ring)


# ---- atomic Prometheus publish ------------------------------------------


def test_prom_reader_never_sees_partial_file(tmp_path, monkeypatch):
    """Regression for the atomic textfile contract: a reader polling
    mid-write sees either the old or the new complete file — never a
    torn prefix. The writer rewrites a 200-gauge file as fast as it
    can while the reader parses continuously; every parsed snapshot
    must be internally complete (all gauges of ONE generation)."""
    from horovod_tpu.obs import export as exp_mod
    from horovod_tpu.obs import registry as reg_mod

    monkeypatch.setenv("HVDTPU_METRICS", "1")
    reg_mod._registry.reset()
    reg_mod._enabled = None
    rep = exp_mod.MetricsReporter(directory=str(tmp_path), interval=0.0)
    reg = reg_mod.metrics()
    n_gauges = 200

    def publish(gen):
        for i in range(n_gauges):
            reg.gauge(f"atomic.g{i}").set(gen)
        rep.flush(summarize=False)

    publish(0)
    prom = rep.prom_path()
    stop = threading.Event()
    errors = []

    def writer():
        gen = 1
        while not stop.is_set():
            publish(gen)
            gen += 1

    def reader():
        while not stop.is_set():
            try:
                text = open(prom).read()
            except FileNotFoundError:
                errors.append("prom file vanished")
                break
            lines = [
                l for l in text.splitlines()
                if l.startswith("hvdtpu_atomic_g") and not l.startswith("#")
            ]
            if len(lines) != n_gauges:
                errors.append(f"torn read: {len(lines)} gauges")
                break
            gens = {l.rsplit(" ", 1)[1] for l in lines}
            if len(gens) != 1:
                errors.append(f"mixed generations in one read: {gens}")
                break
            if not text.endswith("\n"):
                errors.append("file does not end in newline")
                break

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start(), r.start()
    time.sleep(1.0)
    stop.set()
    w.join(5), r.join(5)
    reg_mod._registry.reset()
    reg_mod._enabled = None
    assert not errors, errors


# ---- metric-name lint ----------------------------------------------------


def test_metric_names_lint_clean():
    """The in-tree state passes both rules (the sixth lint gate)."""
    ml = _load_tool("check_metric_names")
    assert ml.check_ownership() == []
    assert ml.check_docs() == []


def test_metric_names_lint_catches_drift(tmp_path, monkeypatch):
    ml = _load_tool("check_metric_names")
    pkg = tmp_path / "horovod_tpu"
    pkg.mkdir()
    (pkg / "a.py").write_text('m.counter("dup.series").inc()\n')
    (pkg / "b.py").write_text(
        'm.counter("dup.series").inc()\n'
        'm.gauge(f"dyn.{host}").set(1)\n'
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "api.md").write_text("`dyn.<host>` is documented here\n")
    monkeypatch.setattr(ml, "REPO", str(tmp_path))
    owned = ml.check_ownership()
    assert [name for name, _ in owned] == ["dup.series"]
    assert len(owned[0][1]) == 2
    # dup.series is undocumented; the dynamic name matches by prefix.
    assert ml.check_docs() == ["dup.series"]


# ---- end-to-end: seeded hang ships a usable timeline --------------------


def _merged_trace(res):
    ht = _load_tool("hvdtpu_trace")
    trace_dir = res["trace_dir"]
    merged = ht.merge_dir(
        trace_dir, out=os.path.join(trace_dir, "merged.json")
    )
    assert merged is not None, f"no flight-recorder dumps in {trace_dir}"
    assert ht.validate_events(merged["traceEvents"]) == []
    return ht, merged


def test_hang_scenario_flight_recorder_end_to_end():
    """The acceptance scenario: a 2-worker elastic run with tracing
    armed and an injected ``worker.step:hang`` produces per-rank
    flight-recorder dumps that merge into one valid Perfetto JSON in
    which the chaos injection instant, the victim's last OPEN step
    span, and the driver's lease-expiry span are all present and
    clock-ordered (injection before expiry on the aligned clock)."""
    import tools.chaos_soak as soak

    # 20 s alone; beside five other xdist workers compiling, 150 s was
    # missed twice (PR 36), so it takes the 240 s its sibling scenarios have
    res = soak.run_scenario("hang", steps=5, timeout=240.0)
    problems = soak.check_invariants(res, steps=5)
    assert not problems, problems
    ht, merged = _merged_trace(res)
    stems = merged["metadata"]["merged_from"]
    assert "driver" in stems
    assert len([s for s in stems if s != "driver"]) >= 2, stems
    events = merged["traceEvents"]
    chaos_fires = [
        e for e in events
        if e["name"] == "chaos.worker.step"
        and e.get("args", {}).get("action") == "hang"
    ]
    assert chaos_fires, "chaos injection instant missing from the merge"
    open_steps = [
        e for e in events if e["ph"] == "B" and e["name"] == "worker.step"
    ]
    assert open_steps, "victim's open step span missing (flight dump)"
    expiries = [e for e in events if e["name"] == "lease.expiry"]
    assert expiries, "driver's lease-expiry span missing"
    # Clock-aligned ordering: the injection precedes the lease expiry's
    # END (start may precede the fire — the lease span covers the whole
    # silent window), and the victim's open span is clock-plausible.
    fire_ts = min(e["ts"] for e in chaos_fires)
    expiry_end = max(e["ts"] + e["dur"] for e in expiries)
    assert fire_ts <= expiry_end
    assert min(e["ts"] for e in open_steps) <= fire_ts
    # The victim observed the driver's clock at join: its offset was
    # recovered (same machine, so it must be sub-second).
    offs = merged["metadata"]["clock_offsets_us"]
    victim_offsets = [
        off for stem, off in offs.items()
        if stem != "driver" and off is not None
    ]
    assert victim_offsets, f"no clock_sync observations: {offs}"
    assert all(abs(off) < 2_000_000 for off in victim_offsets), offs


def test_deadline_diagnostics_attach_flight_recorder():
    """When a scenario blows its deadline, the diagnostics bundle
    carries the merged flight-recorder timeline — and it exists on
    disk and parses (the satellite's seeded-hang deadline contract).
    The hang scenario cannot finish in 6 s, so the deadline fires
    deterministically; the teardown SIGTERMs are what make the wedged
    processes dump."""
    import tools.chaos_soak as soak

    res = soak.run_scenario("hang", steps=5, timeout=6.0)
    assert res["timed_out"]
    fr = (res["diagnostics"] or {}).get("flight_recorder")
    assert fr, f"diagnostics carry no flight recorder: {res['diagnostics']}"
    assert "error" not in fr, fr
    assert os.path.exists(fr["merged"])
    doc = json.load(open(fr["merged"]))
    ht = _load_tool("hvdtpu_trace")
    assert ht.validate_events(doc["traceEvents"]) == []
    assert fr["events"] > 0 and fr["files"]
