"""``lib/scopes.py``: labels to phases and kernels, mixed fusions from the
compiled HLO, the split of a hand-built trace, and the fixtures cut from
``split.py`` runs on the chip (``make_split_fixture.py``)."""

import glob
import gzip
import json
import os

import pytest

from benchmark.lib import scopes, trace as T

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
DEV, HOST = "/device:TPU:0", "/host:CPU"
STEP = "jit(hvd_train_step)/shard_map"


@pytest.mark.parametrize("label,phase", [
    (f"{STEP}/hvd_grad/jvp(GPT2LMModel)/transformer/wte.attend/dot_general",
     "forward"),
    (f"{STEP}/hvd_grad/transpose(jvp(GPT2LMModel))/transformer/convert",
     "backward"),
    (f"{STEP}/hvd_reduce/psum", "reduce"),
    (f"{STEP}/hvd_update/add", "update"),
    (f"{STEP}/hvd_loss_avg/div", "loss_avg"),
    (STEP, "unscoped"),
    ("", "unlabelled"),
    # a module that merely has a scope's name inside its own is not it
    (f"{STEP}/my_hvd_update_layer/add", "unscoped"),
])
def test_phase_of(label, phase):
    assert scopes.phase_of(label) == phase


def test_kernel_of_reads_the_name_given_to_pallas_call():
    label = (f"{STEP}/hvd_grad/jvp(GPT2LMModel)/transformer/block_0/"
             "MultiHeadAttention_0/hvd_flash_fwd/pallas_call")
    assert scopes.kernel_of(label, "x.1") == "hvd_flash_fwd"
    assert scopes.kernel_of(label[:-len("/pallas_call")], "x.1") == (
        "hvd_flash_fwd"
    )
    assert scopes.kernel_of("", "custom-call.7") == "custom-call.7"


HLO = f"""
HloModule jit_hvd_train_step

%fused_computation.13 (p0: f32[8], p1: f32[8]) -> f32[8] {{
  %p0 = f32[8] parameter(0)
  %p1 = f32[8] parameter(1)
  %c = f32[] constant(2), metadata={{op_name="{STEP}"}}
  %dot.1 = f32[8] multiply(%p0, %p1), metadata={{op_name="{STEP}/hvd_grad/transpose(jvp(M))/wte/dot_general"}}
  ROOT %add.9 = f32[8] add(%dot.1, %p1), metadata={{op_name="{STEP}/hvd_update/add"}}
}}

%fused_computation.2 (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8] parameter(0)
  ROOT %neg = f32[8] negate(%p0), metadata={{op_name="{STEP}/hvd_update/neg"}}
}}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {{
  %a = f32[8] parameter(0)
  %b = f32[8] parameter(1)
  %fusion.13 = f32[8] fusion(%a, %b), kind=kLoop, calls=%fused_computation.13, metadata={{op_name="{STEP}/hvd_update/add"}}
  ROOT %fusion.2 = f32[8] fusion(%fusion.13), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{STEP}/hvd_update/neg"}}
}}
"""


def test_fusion_phases_finds_the_fusion_that_mixes():
    found = scopes.fusion_phases(HLO)
    assert found == {
        "fusion.13": {"backward": 1, "update": 1},  # the constant mixes nothing
        "fusion.2": {"update": 1},
    }
    assert scopes.scopes_inside(found["fusion.13"]) == ("grad", "update")
    # forward and backward are one scope: such a fusion mixes nothing
    assert scopes.scopes_inside({"forward": 2, "backward": 5}) == ("grad",)


def _events():
    """Two steps of 10 ms on one device, times in ns: the window runs from
    the first sync's end (0) to the last one's (20 ms)."""
    ms = 1e6
    dev = [  # name, start, duration (ms)
        ("fusion.1", 0.0, 2.0),        # forward
        ("hvd_flash_fwd.3", 2.0, 1.0),  # kernel
        ("fusion.2", 3.0, 3.0),        # backward
        ("all-reduce.1", 6.0, 1.0),    # collective
        ("fusion.13", 7.0, 2.0),       # update, mixes
        ("copy.5", 9.0, 0.3),          # unscoped: a label with no scope
        ("copy-done.7", 9.3, 0.2),     # unlabelled: the compiler's own
    ]
    events = []
    for step in range(2):
        for name, s, d in dev:
            events.append([DEV, T.OP_LINE, name, (10 * step + s) * ms, d * ms])
    host = [
        ("sync", -1.0, 1.0), ("sync", 9.0, 1.0), ("sync", 19.0, 1.0),
        ("dispatch", 0.5, 3.0), ("hvd.step.dispatch", 0.6, 2.8),
        ("hvd.step.jit", 0.7, 2.6),
        # over the first idle gap (9.5..10): sync longest, then the
        # program's own span inside it, which is the innermost
        ("hvd.step.sync", 9.4, 0.6),
        ("dispatch", 10.5, 3.0), ("hvd.step.dispatch", 10.6, 2.8),
    ]
    for name, s, d in host:
        events.append([HOST, "python", name, s * ms, d * ms])
    labels = {
        "fusion.1": f"{STEP}/hvd_grad/jvp(M)/dense/dot_general",
        "hvd_flash_fwd.3": f"{STEP}/hvd_grad/jvp(M)/attn/hvd_flash_fwd/pallas_call",
        "fusion.2": f"{STEP}/hvd_grad/transpose(jvp(M))/dense/dot_general",
        "all-reduce.1": f"{STEP}/hvd_reduce/psum",
        "fusion.13": f"{STEP}/hvd_update/add",
        "copy.5": f"{STEP}/copy",
    }
    return events, labels


def test_split_puts_every_operation_in_exactly_one_place():
    events, labels = _events()
    r = scopes.split(
        events, labels, kernel_names=["hvd_flash_fwd.3"],
        collective_names=["all-reduce.1"],
        mixed={"fusion.13": {"backward": 1, "update": 1},
               "fusion.2": {"backward": 3, "forward": 1}},
    )
    assert r["steps"] == 2 and len(r["devices"]) == 1
    d = r["devices"][0]
    assert d["phases_ms"] == pytest.approx({
        "forward": 2.0, "backward": 3.0, "reduce": 0.0, "update": 2.0,
        "loss_avg": 0.0, "unscoped": 0.3, "unlabelled": 0.2,
    })
    assert d["kernels_ms"] == pytest.approx({"hvd_flash_fwd": 1.0})
    assert d["collectives_ms"] == pytest.approx(1.0)
    assert d["mixed_ms"]["update"] == pytest.approx(2.0)
    assert d["mixed_with_ms"] == pytest.approx({"grad+update": 2.0})
    assert d["mixed_ms"]["backward"] == 0.0  # one scope inside: not mixed
    total = (sum(d["phases_ms"].values()) + sum(d["kernels_ms"].values())
             + d["collectives_ms"])
    assert total == pytest.approx(d["busy_ms"]) == pytest.approx(9.5)
    assert d["window_ms"] == pytest.approx(10.0)
    # the gap the program's span lies over goes to it, the other to the loop
    assert d["idle_ms_by_span"] == pytest.approx(
        {"hvd.step.sync": 0.25, "sync": 0.25}
    )
    assert "hvd.step.jit" in r["host_spans_ms"]
    text = scopes.table(r, busy_ms_benchmark=9.5)
    assert "hvd_flash_fwd" in text and "unscoped" in text


def test_split_agrees_with_the_benchmarks_reduction():
    """Busy, kernels and collectives are ``lib/trace.py``'s own."""
    events, labels = _events()
    kw = dict(kernel_names=["hvd_flash_fwd.3"],
              collective_names=["all-reduce.1"])
    ours = scopes.split(events, labels, **kw)["devices"][0]
    loop_only = [e for e in events if not e[2].startswith("hvd.")]
    theirs = T.summarize(loop_only, **kw)
    assert ours["busy_ms"] == pytest.approx(theirs.per_step_ms("busy_s"))
    assert sum(ours["kernels_ms"].values()) == pytest.approx(
        theirs.per_step_ms("kernels_s"))
    assert ours["collectives_ms"] == pytest.approx(
        theirs.per_step_ms("collective_s"))
    assert (sum(ours["phases_ms"].values())
            == pytest.approx(theirs.per_step_ms("other_s")))


def test_split_without_a_device_plane_says_so():
    events = [e for e in _events()[0] if e[0] != DEV]
    with pytest.raises(ValueError, match="XLA Ops"):
        scopes.split(events, {})


CHIP_FIXTURES = sorted(glob.glob(os.path.join(FIXTURES, "*.split.json.gz")))


@pytest.mark.parametrize(
    "path", CHIP_FIXTURES,
    ids=[os.path.basename(p)[:-len(".split.json.gz")] for p in CHIP_FIXTURES],
)
def test_chip_fixture_reads_as_on_the_day(path):
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    with open(path[:-len(".json.gz")] + ".expected.json") as f:
        want = json.load(f)
    r = scopes.split(
        d["events"], d["labels"], kernel_names=d["kernel_names"],
        collective_names=d["collective_names"], mixed=d["mixed"],
    )
    assert r["steps"] == want["steps"] == 3
    assert len(r["devices"]) == want["devices"]
    got = max(r["devices"], key=lambda dev: dev["busy_ms"])
    for key in ("busy_ms", "collectives_ms"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    for key in ("phases_ms", "mixed_ms", "mixed_with_ms", "kernels_ms",
                "idle_ms_by_span"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    # what the PR was accepted on: nearly nothing unscoped, and the parts
    # sum to the whole
    assert got["phases_ms"]["unscoped"] < 0.02 * got["busy_ms"]
    total = (sum(got["phases_ms"].values())
             + sum(got["kernels_ms"].values()) + got["collectives_ms"])
    assert total == pytest.approx(got["busy_ms"], rel=0.01)
    # and the benchmark's own reduction reads the same busy time
    loop_only = [e for e in d["events"] if not e[2].startswith("hvd.")]
    theirs = T.summarize(
        loop_only, kernel_names=d["kernel_names"],
        collective_names=d["collective_names"],
    )
    assert got["busy_ms"] == pytest.approx(
        theirs.per_step_ms("busy_s"), rel=1e-9)
