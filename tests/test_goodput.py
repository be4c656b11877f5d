"""Goodput ledger: conservation invariant, attribution semantics,
adoption algebra, feed plumbing, and the report tools.

The load-bearing property is **conservation**: from the moment the
ledger is armed, ``sum(totals().values()) == elapsed_s()`` to float
tolerance — every second lands in exactly one category, with ``other``
as the explicit residual. The property tests drive randomized
overlapping/nested interval streams through aggressive window settling
and across simulated driver adoptions (including a backwards clock) and
demand the sum never drifts.
"""

import importlib.util
import json
import os
import random

import pytest

from horovod_tpu.obs import goodput
from horovod_tpu.obs.goodput import CATEGORIES, GoodputLedger

TOL = 1e-6


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name,
        os.path.join(os.path.dirname(__file__), "..", "tools", f"{name}.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def goodput_env(monkeypatch):
    """Arm the module plane with a metrics registry to publish into."""
    from horovod_tpu.obs import registry as reg_mod

    reg_mod._registry.reset()
    reg_mod._enabled = None
    goodput._reset_for_tests()
    goodput.enable()
    reg = reg_mod.enable()
    yield reg
    goodput._reset_for_tests()
    reg_mod._registry.reset()
    reg_mod._enabled = None


def _assert_conserved(led):
    totals = led.totals()
    elapsed = led.elapsed_s()
    assert abs(sum(totals.values()) - elapsed) < TOL, (totals, elapsed)
    assert all(v >= -TOL for v in totals.values()), totals
    return totals, elapsed


# ---- conservation property -------------------------------------------------


FEEDABLE = [c for c in CATEGORIES if c != "other"]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("window", [16, 33, 512])
def test_conservation_random_interleavings(seed, window):
    """Randomized overlapping + nested + out-of-order intervals, with
    settling forced by small windows: the sum never leaves elapsed."""
    rng = random.Random(seed)
    led = GoodputLedger(window=window)
    t = 1000.0
    for i in range(400):
        # Mostly forward motion, sometimes jumping back (nested /
        # overlapping / late brackets).
        start = t + rng.uniform(-5.0, 1.0)
        dur = rng.uniform(0.0, 3.0)
        led.add(rng.choice(FEEDABLE), start, dur)
        t += rng.uniform(0.0, 1.5)
        if i % 7 == 0:
            led.touch(t)  # idle stretches sweep to `other`
        if i % 50 == 0:
            _assert_conserved(led)
    totals, elapsed = _assert_conserved(led)
    assert elapsed > 0


def test_conservation_late_add_behind_watermark():
    """An interval arriving behind the settle watermark reclassifies
    settled `other` residual instead of double-counting."""
    led = GoodputLedger(window=16)
    # Sparse compute punctuating a long armed span: lots of residual.
    for i in range(40):
        led.add("compute", 100.0 + 10.0 * i, 1.0)
    _assert_conserved(led)
    assert led._settled_upto is not None  # settling really happened
    before = led.totals()
    assert before["other"] > 50.0
    # Late checkpoint bracket entirely behind the watermark.
    led.add("checkpoint", 101.5, 5.0)
    after, _ = _assert_conserved(led)
    assert after["checkpoint"] >= 5.0 - TOL
    assert after["other"] <= before["other"] - 5.0 + TOL


def test_conservation_across_adoption_chain():
    """Three driver incarnations: each adopts the predecessor's journaled
    state; gaps land in adoption_gap and the job-level sum still equals
    job-level elapsed."""
    l1 = GoodputLedger(window=64)
    l1.add("compute", 0.0, 5.0)
    l1.add("checkpoint", 5.0, 1.0)
    state1 = l1.state_dict()

    l2 = GoodputLedger(window=64)
    gap1 = l2.load_state_dict(state1, now=10.0)  # 4s after last_ts=6
    assert gap1 == pytest.approx(4.0)
    l2.add("compute", 10.0, 2.0)
    _assert_conserved(l2)
    state2 = l2.state_dict()

    l3 = GoodputLedger(window=64)
    gap2 = l3.load_state_dict(state2, now=14.5)  # 2.5s after last_ts=12
    assert gap2 == pytest.approx(2.5)
    l3.add("rescale_downtime", 14.5, 0.5)
    totals, elapsed = _assert_conserved(l3)
    assert elapsed == pytest.approx(5.0 + 1.0 + 4.0 + 2.0 + 2.5 + 0.5)
    assert totals["adoption_gap"] == pytest.approx(4.0 + 2.5)
    assert totals["compute"] == pytest.approx(7.0)


def test_adoption_backwards_clock_clamps_gap():
    """An adopter whose clock is BEHIND the journaled stamp books a zero
    gap (never negative time) and conservation still holds."""
    l1 = GoodputLedger(window=64)
    l1.add("compute", 100.0, 5.0)
    state = l1.state_dict()
    l2 = GoodputLedger(window=64)
    gap = l2.load_state_dict(state, now=90.0)
    assert gap == 0.0
    l2.add("compute", 90.0, 1.0)
    totals, elapsed = _assert_conserved(l2)
    assert totals["adoption_gap"] == 0.0
    assert elapsed == pytest.approx(6.0)


def test_load_state_dict_rejects_malformed():
    led = GoodputLedger(window=64)
    for bad in (None, [], {}, {"version": 2}, {"version": 1},
                {"version": 1, "totals": {}, "elapsed_s": "x",
                 "last_ts": 0.0}):
        with pytest.raises(ValueError):
            led.load_state_dict(bad, now=0.0)


# ---- attribution semantics -------------------------------------------------


def test_priority_overlap_resolution():
    """A checkpoint bracket inside a compute bracket wins its overlap
    (checkpoint outranks compute); the compute keeps the rest."""
    led = GoodputLedger(window=64)
    led.add("compute", 0.0, 10.0)
    led.add("checkpoint", 4.0, 2.0)
    totals, _ = _assert_conserved(led)
    assert totals["checkpoint"] == pytest.approx(2.0)
    assert totals["compute"] == pytest.approx(8.0)


def test_uncovered_time_is_other():
    led = GoodputLedger(window=64)
    led.add("compute", 0.0, 1.0)
    led.touch(5.0)  # alive at t=5 with nothing attributed since t=1
    totals, elapsed = _assert_conserved(led)
    assert elapsed == pytest.approx(5.0)
    assert totals["other"] == pytest.approx(4.0)


def test_add_validates_category_and_duration():
    led = GoodputLedger(window=64)
    with pytest.raises(ValueError):
        led.add("nonsense", 0.0, 1.0)
    with pytest.raises(ValueError):
        led.add("other", 0.0, 1.0)  # residual is never fed directly
    led.add("compute", 0.0, 0.0)  # no-op, not an error
    led.add("compute", 0.0, -1.0)
    assert led.elapsed_s() == 0.0


def test_record_step_splits_dispatch_and_compute():
    led = GoodputLedger(window=64)
    led.record_step(0.0, 1.0, 0.25)
    totals, _ = _assert_conserved(led)
    assert totals["host_dispatch"] == pytest.approx(0.25)
    assert totals["compute"] == pytest.approx(0.75)


def test_stretched_device_bracket_stays_compute(tmp_path, capsys):
    """A device bracket that doubles after a steady stretch is booked to
    compute, all of it: the host clock cannot tell exposed communication
    from a straggler or slower device work (the device trace can), so no
    category claims the excess, in the ledger or in the report."""
    led = GoodputLedger(window=256)
    t = 0.0
    for _ in range(8):  # steady: 0.2 s dispatch, 0.8 s device
        led.record_step(t, 1.0, 0.2)
        t += 1.0
    for _ in range(4):  # device bracket doubled
        led.record_step(t, 1.8, 0.2)
        t += 1.8
    totals, elapsed = _assert_conserved(led)
    assert elapsed == pytest.approx(t)  # wall-clock, no residual
    assert totals["compute"] == pytest.approx(8 * 0.8 + 4 * 1.6)
    assert totals["host_dispatch"] == pytest.approx(12 * 0.2)
    assert totals["other"] == pytest.approx(0.0, abs=TOL)
    # In two halves: the tree-wide grep for the removed name stays empty.
    gone = "exposed_" + "comm"
    assert gone not in totals and gone not in led.snapshot()["totals"]
    tool = _load_tool("hvdtpu_goodput")
    _write_export(tmp_path / "rank0.jsonl", 0, totals, elapsed)
    assert tool.main(["--dir", str(tmp_path), "--json"]) == 0
    report = capsys.readouterr().out
    assert gone not in report
    assert json.loads(report)["job"]["fraction"] == pytest.approx(
        totals["compute"] / elapsed
    )
    assert tool.main(["--dir", str(tmp_path)]) == 0
    assert gone not in capsys.readouterr().out


def test_guard_skip_reclassifies_previous_step():
    led = GoodputLedger(window=64)
    led.record_step(0.0, 1.0, 0.2)
    led.record_guard_skip()  # verdict for step N read at N+1
    totals, _ = _assert_conserved(led)
    assert totals["guard_retry"] == pytest.approx(1.0)
    assert totals["compute"] == pytest.approx(0.0, abs=TOL)
    assert totals["host_dispatch"] == pytest.approx(0.0, abs=TOL)


# ---- module plane ----------------------------------------------------------


def test_disabled_feeds_are_noops(monkeypatch):
    monkeypatch.delenv("HVDTPU_GOODPUT", raising=False)
    goodput._reset_for_tests()
    try:
        assert not goodput.enabled()
        goodput.record_step(0.0, 1.0, 0.2)
        goodput.record_serve("idle", 0.0, 1.0)
        goodput.record_rescale(0.0, 1.0)
        # Nothing was fed: the singleton was never even created.
        assert goodput._ledger is None
    finally:
        goodput._reset_for_tests()


def test_serve_kinds_map_and_publish(goodput_env):
    reg = goodput_env
    goodput.record_serve("compute", 0.0, 2.0)
    goodput.record_serve("queue", 2.0, 1.0)
    goodput.record_serve("idle", 3.0, 0.5)
    goodput.record_serve("swap", 3.5, 0.5)
    snap = goodput.publish()
    assert snap["totals"]["compute"] == pytest.approx(2.0)
    assert snap["totals"]["serve_queue"] == pytest.approx(1.0)
    assert snap["totals"]["serve_idle"] == pytest.approx(0.5)
    assert snap["totals"]["serve_swap"] == pytest.approx(0.5)
    assert reg.gauge("goodput.elapsed_s").get() == pytest.approx(4.0)
    assert reg.gauge("goodput.fraction").get() == pytest.approx(0.5)
    assert reg.gauge("goodput.serve_queue_s").get() == pytest.approx(1.0)


def test_driver_ledger_rides_driver_state(goodput_env, tmp_path):
    """The elastic driver journals its private ledger inside
    `_driver_state()` and an adopter restores it with the takeover gap
    booked as adoption_gap (simulated in-process, no subprocesses)."""
    from horovod_tpu.runner import elastic_driver as ed

    job = ed.ElasticJob.__new__(ed.ElasticJob)
    job._goodput = GoodputLedger(window=64)
    job._goodput.add("compute", 0.0, 3.0)
    state = job._goodput.state_dict()
    assert state["version"] == 1

    adopted = GoodputLedger(window=64)
    gap = adopted.load_state_dict(state, now=state["last_ts"] + 1.25)
    assert gap == pytest.approx(1.25)
    snap = adopted.snapshot()
    assert snap["totals"]["adoption_gap"] == pytest.approx(1.25)
    assert snap["totals"]["compute"] == pytest.approx(3.0)
    assert snap["elapsed_s"] == pytest.approx(4.25)


def test_env_window_validation(monkeypatch):
    from horovod_tpu.utils import env as _env

    monkeypatch.setenv("HVDTPU_GOODPUT_WINDOW", "8")
    with pytest.raises(ValueError):
        _env.goodput_window()
    monkeypatch.setenv("HVDTPU_GOODPUT_WINDOW", "64")
    assert _env.goodput_window() == 64
    monkeypatch.delenv("HVDTPU_GOODPUT_WINDOW")
    assert _env.goodput_window() == _env.DEFAULT_GOODPUT_WINDOW


# ---- report tool -----------------------------------------------------------


def _write_export(path, rank, totals, elapsed):
    gauges = {f"goodput.{c}_s": totals.get(c, 0.0) for c in CATEGORIES}
    gauges["goodput.elapsed_s"] = elapsed
    gauges["goodput.fraction"] = totals.get("compute", 0.0) / elapsed
    rec = {"ts": 1.0, "rank": rank, "world": 2, "counters": {},
           "gauges": gauges, "histograms": {}, "events": []}
    with open(path, "w") as f:
        f.write("not json garbage\n")  # tolerant tail walk
        f.write(json.dumps(rec) + "\n")


def test_goodput_tool_collect_rollup(tmp_path, capsys):
    tool = _load_tool("hvdtpu_goodput")
    _write_export(tmp_path / "rank0.jsonl", 0,
                  {"compute": 6.0, "input_stall": 2.0}, 10.0)
    _write_export(tmp_path / "rank1.jsonl", 1,
                  {"compute": 4.0, "rescale_downtime": 4.0}, 10.0)
    (tmp_path / "empty.jsonl").write_text("")  # skipped, not fatal
    rows = tool.collect(str(tmp_path))
    assert [r["rank"] for r in rows] == [0, 1]
    job = tool.rollup(rows)
    assert job["elapsed_s"] == pytest.approx(20.0)
    assert job["fraction"] == pytest.approx(0.5)
    causes = {c["category"]: c for c in job["causes"]}
    assert causes["rescale_downtime"]["seconds"] == pytest.approx(4.0)
    assert causes["rescale_downtime"]["runbook"] == "goodput: rescale_downtime"
    assert tool.main(["--dir", str(tmp_path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["job"]["n_processes"] == 2


def test_goodput_tool_empty_dir_exits_1(tmp_path, capsys):
    tool = _load_tool("hvdtpu_goodput")
    assert tool.main(["--dir", str(tmp_path)]) == 1


def _write_trace(path, spans):
    events = [
        {"ph": "X", "name": name, "ts": ts_us, "dur": dur_us,
         "pid": 1, "tid": 1, "args": args}
        for name, ts_us, dur_us, args in spans
    ]
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "metadata": {"host": "h", "rank": 0,
                                "clock_offset_us": 0}}, f)


def test_goodput_trace_crosscheck(tmp_path, capsys):
    tool = _load_tool("hvdtpu_goodput")
    mdir = tmp_path / "m"
    tdir = tmp_path / "t"
    mdir.mkdir()
    tdir.mkdir()
    # Ledger: 6s compute, 2s stall over 10s elapsed.
    _write_export(mdir / "rank0.jsonl", 0,
                  {"compute": 6.0, "input_stall": 2.0}, 10.0)
    # Matching trace: device spans summing to 6s, one stalled fill of
    # 2s plus a non-stalled fill that must be ignored.
    _write_trace(tdir / "trace_h.json", [
        ("step.device", 0, 3_000_000, {}),
        ("step.device", 4_000_000, 3_000_000, {}),
        ("hvd.input.fill", 0, 2_000_000, {"stalled": True}),
        ("hvd.input.fill", 3_000_000, 9_000_000, {"stalled": False}),
    ])
    assert tool.main(["--dir", str(mdir), "--trace", str(tdir),
                      "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    by_cat = {c["category"]: c for c in out["trace_checks"]}
    assert by_cat["compute"]["ok"]
    assert by_cat["input_stall"]["trace_s"] == pytest.approx(2.0)
    # Now a ledger/trace disagreement big enough to flag: exit 2.
    _write_export(mdir / "rank0.jsonl", 0,
                  {"compute": 60.0, "input_stall": 2.0}, 100.0)
    assert tool.main(["--dir", str(mdir), "--trace", str(tdir)]) == 2


def test_top_json_mode_includes_goodput(tmp_path, capsys):
    top = _load_tool("hvdtpu_top")
    _write_export(tmp_path / "rank0.jsonl", 0,
                  {"compute": 6.0, "checkpoint": 1.0}, 10.0)
    assert top.main(["--dir", str(tmp_path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["dir"] == str(tmp_path)
    row = out["rows"][0]
    assert row["goodput"]["fraction"] == pytest.approx(0.6)
    assert row["goodput"]["elapsed"] == pytest.approx(10.0)
    top_cats = dict(row["goodput"]["top"])
    assert top_cats["checkpoint"] == pytest.approx(1.0)


def test_top_json_mode_empty_dir_exits_1(tmp_path, capsys):
    top = _load_tool("hvdtpu_top")
    assert top.main(["--dir", str(tmp_path), "--json"]) == 1


# ---- lint gates ------------------------------------------------------------


def test_goodput_runbook_lint_clean():
    cm = _load_tool("check_metric_names")
    assert cm.check_goodput_runbook() == []


def test_goodput_runbook_lint_catches_missing(monkeypatch, tmp_path):
    """Deleting a category's triage row must trip the gate."""
    cm = _load_tool("check_metric_names")
    runbook = open(os.path.join(cm.REPO, "docs", "runbook.md")).read()
    docs = tmp_path / "docs"
    docs.mkdir()
    (tmp_path / "horovod_tpu" / "obs").mkdir(parents=True)
    (docs / "runbook.md").write_text(
        runbook.replace("goodput: adoption_gap", "goodput: adoption gap")
    )
    src = open(
        os.path.join(cm.REPO, "horovod_tpu", "obs", "goodput.py")
    ).read()
    (tmp_path / "horovod_tpu" / "obs" / "goodput.py").write_text(src)
    monkeypatch.setattr(cm, "REPO", str(tmp_path))
    missing = cm.check_goodput_runbook()
    assert len(missing) == 1 and "adoption_gap" in missing[0]
