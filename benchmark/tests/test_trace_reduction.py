"""The reduction on event lists small enough to work out by hand, then on
the fixtures recorded on the chip."""

import glob
import os

import pytest

from benchmark.lib import trace as T

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_union_length_clip_subtract():
    iv = [(0, 10), (5, 12), (20, 30), (30, 31), (40, 40)]
    assert T.union(iv) == [(0, 12), (20, 31)]
    assert T.length(iv) == 23
    assert T.clip(iv, 8, 25) == [(8, 10), (8, 12), (20, 25)]
    assert T.subtract([(0, 100)], [(10, 20), (15, 30), (90, 120)]) == [
        (0, 10), (30, 90)
    ]
    assert T.subtract([(0, 10), (20, 30)], []) == [(0, 10), (20, 30)]
    assert T.subtract([(0, 10)], [(0, 10)]) == []


def test_collective_names_and_pairs():
    assert T.is_collective("all-reduce.3")
    assert T.is_collective("all-reduce-start.3")
    assert T.is_collective("reduce-scatter")
    assert not T.is_collective("fusion.12")
    assert not T.is_collective("all-reduce-fusion")  # only by the HLO names
    assert T.is_collective("fusion.9", {"fusion.9"})
    ops = [
        ("all-reduce-start.1", 10, 11), ("fusion.1", 11, 30),
        ("all-reduce-done.1", 30, 35), ("all-gather.2", 50, 60),
        ("all-reduce-done.7", 70, 72),  # its start lies before the trace
    ]
    assert T.collective_intervals(ops) == [(10, 35), (50, 60), (70, 72)]


def test_event_names_are_cut_to_the_instruction_name():
    text = ("%fusion.13 = (f32[50257,768]{1,0:T(8,128)}, f32[8]) fusion("
            "f32[8] %p.1, bf16[4] %copy-done.3), kind=kOutput, calls=%fc.15")
    assert T.instruction_name(text) == "fusion.13"
    assert T.instruction_name("%all-reduce-start.2 = f32[8] all-reduce-"
                              "start(f32[8] %g)") == "all-reduce-start.2"
    assert T.instruction_name("fusion.13") == "fusion.13"
    assert T.instruction_name("jit__step(123)") == "jit__step(123)"


def test_async_line_gives_a_collective_its_whole_interval():
    # the op line shows only the issue of the start and the wait of the
    # done; the asynchronous line shows start to done under the start's name
    ops = [("all-reduce-start.4", 100, 101), ("fusion.1", 101, 160),
           ("all-reduce-done.4", 180, 200)]
    async_ops = [("all-reduce-start.4", 100, 200), ("copy-start.9", 0, 500)]
    assert T.union(T.collective_intervals(ops, (), async_ops)) == [(100, 200)]
    d = T.summarize_device(0, ops, [], 0, 1000, async_ops=async_ops,
                           unit_per_s=1.0)
    assert d.collective_s == 100
    assert d.collective_exposed_s == 100 - 59  # fusion.1 hides 101..160
    assert d.busy_s == 1 + 59 + 20  # the asynchronous line is not busy time


# One device, window 0..1000 (sync spans end at 0 and at 1000), in ns:
#   fusion.1        0..300
#   custom-call.1 300..400        (a Mosaic kernel)
#   all-reduce.1  400..500        (synchronous: all of it exposed)
#   idle          500..600        host is in `dispatch` 480..590
#   all-reduce-start.2 600..610, fusion.2 610..700, idle 700..720,
#   all-reduce-done.2  720..760   (interval 600..760; other ops cover 90)
#   fusion.3      760..900
#   idle          900..1000       host is in `input_wait` 900..930, `sync` 930..1000
HAND_OPS = [
    ("fusion.1", 0, 300), ("custom-call.1", 300, 400),
    ("all-reduce.1", 400, 500), ("all-reduce-start.2", 600, 610),
    ("fusion.2", 610, 700), ("all-reduce-done.2", 720, 760),
    ("fusion.3", 760, 900),
]
HAND_HOST = [
    ("sync", -50, 0), ("dispatch", 480, 590), ("input_wait", 900, 930),
    ("sync", 930, 1000),
]


def test_device_summary_by_hand():
    d = T.summarize_device(
        0, HAND_OPS, HAND_HOST, 0, 1000, kernel_names={"custom-call.1"},
        unit_per_s=1.0,
    )
    assert d.window_s == 1000
    # busy: 0..500, 600..700, 720..900
    assert d.busy_s == 500 + 100 + 180
    assert d.idle_share == pytest.approx(0.22)
    assert d.kernels_s == 100
    # collectives: 400..500 and 600..760
    assert d.collective_s == 100 + 160
    # exposed: 400..500, 600..610, 700..760 (fusion.2 hides 610..700)
    assert d.collective_exposed_s == 100 + 10 + 60
    # other: fusion.1 + fusion.2 + fusion.3
    assert d.other_s == 300 + 90 + 140
    assert d.top_ops[0] == ["fusion.1", 300]
    assert ["custom-call.1", 100] in d.top_ops
    # gaps, longest first: 500..600 (dispatch overlaps 90), 900..1000
    # (sync overlaps 70, input_wait 30), 700..720 (no span)
    assert d.idle_gaps == [["dispatch", 100], ["sync", 100], ["none", 20]]


def test_ops_are_clipped_to_the_window():
    ops = [("fusion.1", -100, 50), ("fusion.2", 950, 1200)]
    d = T.summarize_device(0, ops, [], 0, 1000, unit_per_s=1.0)
    assert d.busy_s == 100
    assert d.idle_gaps == [["none", 900]]


def _events(device, ops, host):
    rows = [[f"/device:TPU:{device}", T.OP_LINE, n, float(s), float(e - s)]
            for n, s, e in ops]
    rows += [["/host:CPU", "main", n, float(s), float(e - s)]
             for n, s, e in host]
    return rows


def test_summarize_two_devices_worst_and_mean():
    second = [("fusion.1", 0, 1000)]  # never idle
    events = _events(0, HAND_OPS, HAND_HOST) + _events(1, second, [])
    events.append(["/device:TPU:0", "Steps", "1", 0.0, 1000.0])  # ignored
    events.append(["/host:CPU", "main", "other span", 0.0, 5.0])  # ignored
    s = T.summarize(events, kernel_names=["custom-call.1"])
    assert s.steps == 1 and s.window_s == pytest.approx(1000 / 1e9)
    assert [d.device for d in s.devices] == [0, 1]
    assert s.worst("collective_s") == pytest.approx(260 / 1e9)
    assert s.busy_s_mean == pytest.approx((780 + 1000) / 2 / 1e9)
    assert max(d.idle_share for d in s.devices) == pytest.approx(0.22)
    assert s.loop_spans_ms["sync"] == pytest.approx([50 / 1e6, 70 / 1e6])


def test_summarize_refuses_a_trace_without_op_line_or_syncs():
    with pytest.raises(ValueError, match="XLA Ops"):
        T.summarize([["/device:TPU:0", "Steps", "1", 0.0, 1.0]])
    with pytest.raises(ValueError, match="sync"):
        T.summarize(_events(0, HAND_OPS, [("sync", 0, 1)]))


def test_events_round_trip(tmp_path):
    events = _events(0, HAND_OPS, HAND_HOST)
    path = str(tmp_path / "e.json.gz")
    T.save_events(events, path)
    assert T.load_events(path) == events


@pytest.mark.parametrize(
    "path", sorted(glob.glob(os.path.join(FIXTURES, "*.events.json.gz"))),
    ids=os.path.basename,
)
def test_recorded_fixture_reduces(path):
    """Three steps recorded on the chip (PR 23): the invariants any real
    trace must meet, and the readings written beside the fixture."""
    import json

    with open(path.replace(".events.json.gz", ".expected.json")) as f:
        expected = json.load(f)
    s = T.summarize(
        T.load_events(path), kernel_names=expected["kernel_names"],
        collective_names=expected["collective_names"],
    )
    assert s.steps == expected["steps"]
    assert len(s.devices) == expected["devices"]
    for d in s.devices:
        assert 0 < d.busy_s <= d.window_s
        assert d.collective_exposed_s <= d.collective_s + 1e-12
        assert d.kernels_s + d.other_s <= d.busy_s + 1e-12
    for field in ("busy_s", "kernels_s", "collective_s",
                  "collective_exposed_s", "other_s"):
        assert s.worst(field) == pytest.approx(expected[field], rel=1e-9)
    assert (expected["kernels_s"] > 0) == expected["has_kernels"]
    assert (expected["collective_s"] > 0) == expected["has_collectives"]
