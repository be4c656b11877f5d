"""``lib/parts.py`` and the readers that cut ``xla_ops_ms`` by the model's
parts: over a hand-built run (labels and seconds), over the two fixtures
recorded on the chip (PR 24's, whose program named no part yet), and the
entries that list them."""

import glob
import gzip
import json
import os

import pytest

from benchmark.lib import parts, resolve, trace as T

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
STEP = "jit(hvd_train_step)/jit(main)"
FWD = f"{STEP}/hvd_grad/jvp(M)"
BWD = f"{STEP}/hvd_grad/transpose(jvp(M))"

SCOPE_METRICS = {
    "embed_ms": "embed", "norm_ms": "norm", "mlp_ms": "mlp",
    "head_ms": "head", "attn_proj_ms": "attn_proj", "attn_xla_ms": "attn_xla",
}
# every operation of ``xla_ops_ms`` is in exactly one of these
ONE_CHIP_CUT = tuple(SCOPE_METRICS) + (
    "mla_proj_ms", "moe_route_ms", "moe_experts_ms", "flash_relayout_ms",
    "grad_unnamed_ms", "unlabelled_ms",
)
NEW = tuple(SCOPE_METRICS) + (
    "flash_relayout_ms", "reduce_ms", "update_ms", "grad_unnamed_ms",
    "unlabelled_ms",
)

# name -> (label, ms a step). One operation per place an operation can land.
OPS = {
    "fusion.1": (f"{FWD}/embed/wte/gather", 1.0),
    "fusion.2": (f"{BWD}/block_0/norm/LayerNorm_0/mul", 2.0),
    "fusion.3": (f"{FWD}/block_0/MultiHeadAttention_0/attn_proj/query/"
                 "dot_general", 3.0),
    "fusion.4": (f"{FWD}/block_0/MultiHeadAttention_0/attn_xla/exp", 4.0),
    "fusion.5": (f"{BWD}/block_0/MlpBlock_0/mlp/Dense_0/dot_general", 5.0),
    "fusion.6": (f"{FWD}/head/wte.attend/dot_general", 6.0),
    "fusion.7": (f"{FWD}/block_1/attn/mla_proj/q_b/dot_general", 7.0),
    "fusion.8": (f"{FWD}/block_1/ffn/moe_route/top_k", 8.0),
    "fusion.9": (f"{BWD}/block_1/ffn/moe_experts/checkpoint/dot_general",
                 9.0),
    # the multi-token module lies over a part: counted once, under the part
    "fusion.10": (f"{FWD}/mtp/embed/embed/gather", 10.0),
    # the program's own relayout, and a copy at a kernel's door
    "copy.11": (f"{FWD}/block_0/attn/attn_layout/reshape", 11.0),
    "copy.12": (f"{BWD}/block_0/attn/hvd_flash_bwd_dq/pallas_call", 12.0),
    # the kernel itself: flash_ms's, under no part
    "hvd_flash_bwd_dq.13": (
        f"{BWD}/block_0/attn/hvd_flash_bwd_dq/pallas_call", 13.0),
    # the user's loss, a module that merely has a part's name in its own
    "fusion.14": (f"{BWD}/mul", 14.0),
    "fusion.15": (f"{FWD}/my_mlp_layer/dot_general", 15.0),
    "copy-done.16": ("", 16.0),
    "async-collective-done.17": (f"{STEP}/shard_map/hvd_reduce/psum", 17.0),
    "fusion.18": (f"{STEP}/hvd_loss_avg/div", 18.0),
    "fusion.19": (f"{STEP}/hvd_update/add", 19.0),
    # what the collective reader does see is collective_ms's, not ours
    "all-reduce.20": (f"{STEP}/hvd_loss_avg/psum", 20.0),
}
KERNELS = ["hvd_flash_bwd_dq.13"]


def _run(n_devices=1, steps=2):
    """Every operation of ``OPS`` once a step, back to back, on each device;
    ``sync`` spans that bound ``steps`` whole steps."""
    ms = 1e6
    step_ms = sum(t for _, t in OPS.values())
    events = []
    for dev in range(n_devices):
        at = 0.0
        for _ in range(steps):
            for name, (_, t) in OPS.items():
                events.append(
                    [f"/device:TPU:{dev}", T.OP_LINE, name, at * ms, t * ms]
                )
                at += t
    for i in range(steps + 1):
        events.append(["/host:CPU", "python", "sync",
                       (i * step_ms - 1.0) * ms, 1.0 * ms])
    return {
        "trace": T.summarize(events, kernel_names=KERNELS),
        "built": {
            "labels": {n: label for n, (label, _) in OPS.items() if label},
            "pallas_call_names": KERNELS, "collective_names": [],
        },
    }


def _read(name, run):
    return resolve.load_layer_metric(BENCH, name).read(run)


def test_each_reader_over_a_hand_built_run():
    run = _run()
    got = {name: _read(name, run) for name in NEW}
    assert got == pytest.approx({
        "embed_ms": 1.0 + 10.0, "norm_ms": 2.0, "attn_proj_ms": 3.0,
        "attn_xla_ms": 4.0, "mlp_ms": 5.0, "head_ms": 6.0,
        "flash_relayout_ms": 11.0 + 12.0,
        "reduce_ms": 17.0 + 18.0, "update_ms": 19.0,
        # one device: what carries hvd_reduce / hvd_update is counted here
        "grad_unnamed_ms": 14.0 + 15.0 + 17.0 + 18.0 + 19.0,
        "unlabelled_ms": 16.0,
    })
    assert _read("flash_ms", run) == pytest.approx(13.0)
    cut = sum(_read(name, run) for name in ONE_CHIP_CUT)
    assert cut == pytest.approx(_read("xla_ops_ms", run))
    assert cut == pytest.approx(sum(t for _, t in OPS.values()) - 13.0 - 20.0)


def test_on_several_devices_reduce_and_update_stand_apart():
    run = _run(n_devices=4)
    assert _read("grad_unnamed_ms", run) == pytest.approx(14.0 + 15.0)
    cut = sum(_read(n, run)
              for n in ONE_CHIP_CUT + ("reduce_ms", "update_ms"))
    assert cut == pytest.approx(_read("xla_ops_ms", run))


def test_nothing_to_read_is_none_never_zero():
    run = _run()
    for name in list(run["built"]["labels"]):
        if name != "fusion.14":
            del run["built"]["labels"][name]
    for name in SCOPE_METRICS:
        assert _read(name, run) is None, name
    for name in ("flash_relayout_ms", "reduce_ms", "update_ms"):
        assert _read(name, run) is None, name
    assert _read("grad_unnamed_ms", run) == pytest.approx(14.0)
    for name in NEW:
        assert _read(name, {**run, "trace": None}) is None, name


def test_predicates():
    assert parts.under(f"{FWD}/norm/mul", ("norm",))
    assert not parts.under(f"{FWD}/mtp_final_norm/mul", ("norm",))
    assert not parts.under("", parts.MODEL_SCOPES)
    built = _run()["built"]
    assert parts.flash_kernel_scopes(built) == {"hvd_flash_bwd_dq"}
    # another kernel's door is not the flash kernels'
    built = {"labels": {"x.1": f"{STEP}/hvd_update/fused_adamw_update"},
             "pallas_call_names": ["x.1"]}
    assert parts.flash_kernel_scopes(built) == frozenset()
    assert not parts.is_relayout("", {"hvd_flash_fwd"})
    assert len(set(parts.MODEL_SCOPES)) == len(parts.MODEL_SCOPES) == 10


CHIP_FIXTURES = sorted(glob.glob(os.path.join(FIXTURES, "*.split.json.gz")))


@pytest.mark.parametrize(
    "path", CHIP_FIXTURES,
    ids=[os.path.basename(p)[:-len(".split.json.gz")] for p in CHIP_FIXTURES],
)
def test_chip_fixture_is_cut_without_rest(path):
    """PR 24's program named its phases and kernels, no part: the scope
    readers find nothing, and what is there still falls into exactly one
    of the others."""
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    names = dict(kernel_names=rec["kernel_names"],
                 collective_names=rec["collective_names"])
    loop_only = [e for e in rec["events"] if not e[2].startswith("hvd.")]
    run = {
        "trace": T.summarize(loop_only, **names),
        "built": {"labels": rec["labels"],
                  "pallas_call_names": rec["kernel_names"],
                  "collective_names": rec["collective_names"]},
    }
    for name in SCOPE_METRICS:
        assert _read(name, run) is None, name
    several = len(run["trace"].devices) > 1
    cut = ["grad_unnamed_ms", "unlabelled_ms"]
    if rec["kernel_names"]:
        cut.append("flash_relayout_ms")
    else:
        assert _read("flash_relayout_ms", run) is None
    if several:
        cut.append("update_ms")
        assert _read("update_ms", run) == pytest.approx(3.44, abs=0.01)
        # before PR 29 the exchange was synchronous all-reduces, which the
        # collective reader sees: nothing of it is left in xla_ops_ms
        assert _read("reduce_ms", run) is None
        assert _read("collective_ms", run) > 1.0
    got = {name: _read(name, run) for name in cut}
    assert all(v is not None and v > 0 for v in got.values()), got
    # worst device of each against worst device of the whole: equal on one
    # device, within the devices' differences on four
    assert sum(got.values()) == pytest.approx(
        _read("xla_ops_ms", run), rel=1e-9 if not several else 5e-3
    )
    # the compiler's copies at the kernels' doors were there before any
    # part was named
    if rec["kernel_names"]:
        assert 0 < got["flash_relayout_ms"] < got["grad_unnamed_ms"]


def test_the_new_entries_each_list_their_cells_and_have_a_reader():
    manifest = resolve.load_manifest(ROOT)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == [
        "embed_ms", "norm_ms", "mlp_ms", "head_ms", "attn_proj_ms",
        "attn_xla_ms", "flash_relayout_ms", "reduce_ms", "update_ms",
        "grad_unnamed_ms", "unlabelled_ms",
    ]
    for name in NEW:
        entry = by_name[name]
        assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
        assert (entry["source"], entry["unit"], entry["better"],
                entry["moves"]) == (
            "device_trace", "ms/step", "lower", "tokens_per_s_per_chip")
        assert callable(resolve.load_layer_metric(BENCH, name).read)
    # what exists only across chips is listed only there
    for name in ("reduce_ms", "update_ms"):
        assert all(cells[c]["chips"] > 1 for c in by_name[name]["workloads"])
    for name in ("embed_ms", "norm_ms", "mlp_ms", "head_ms",
                 "grad_unnamed_ms", "unlabelled_ms"):
        assert set(by_name[name]["workloads"]) == set(cells)
