"""A later PR adds a cell as files and entries; it edits no file."""

import hashlib
import json
import os
import shutil

import pytest

from benchmark.lib import resolve

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")


def _digests(top):
    out = {}
    for base, _, files in os.walk(top):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()
                ).hexdigest()
    return out


@pytest.fixture
def copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(
        BENCH, root / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "fixtures"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return str(root)


def test_every_cell_of_the_manifest_resolves():
    manifest = resolve.load_manifest(ROOT)
    for w in manifest["workloads"]:
        config = resolve.load_config(ROOT, manifest, w["config"])
        traffic = resolve.load_traffic(BENCH, w["traffic"])
        assert config["name"] == w["config"]
        assert os.path.exists(
            os.path.join(BENCH, "families", traffic["family"] + ".py")
        )
        for group in ("end_to_end", "per_layer"):
            assert resolve.metrics_for(manifest, group, w["name"])
    for m in manifest["per_layer"]:
        assert callable(resolve.load_layer_metric(BENCH, m["name"]).read)


def test_a_new_cell_is_files_and_entries_only(copy):
    bench = os.path.join(copy, "benchmark")
    before = _digests(bench)

    with open(os.path.join(bench, "configs", "gpt2-medium.json"), "w") as f:
        json.dump({"name": "gpt2-medium", "n_layer": 24, "n_embd": 1024,
                   "n_head": 16, "n_positions": 1024, "n_inner": None,
                   "vocab_size": 50257}, f)
    with open(os.path.join(bench, "traffic", "lm-b8-zero1.json"), "w") as f:
        json.dump({"extends": "lm-b16-s1024", "per_chip_batch": 8,
                   "step_kwargs": {"sharded": True, "compression": "int8"}},
                  f)
    with open(os.path.join(bench, "layer_metrics", "steps_seen.py"), "w") as f:
        f.write("def read(run):\n    return run['rate']['steps']\n")
    with open(os.path.join(bench, "families", "echo.py"), "w") as f:
        f.write("def build(config, traffic):\n    return (config, traffic)\n")

    manifest_path = os.path.join(copy, "BENCHMARK.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "gpt2-medium", "source": "https://example.org",
        "file": "benchmark/configs/gpt2-medium.json", "reduced": [],
        "why": "test",
    })
    manifest["workloads"].append({
        "name": "gpt2-medium.b8-zero1", "config": "gpt2-medium",
        "traffic": "lm-b8-zero1", "chips": 4, "why": "test",
    })
    manifest["per_layer"].append({
        "name": "steps_seen", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "step builder",
        "moves": "tokens_per_s_per_chip",
        "workloads": ["gpt2-medium.b8-zero1"],
    })
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)

    manifest = resolve.load_manifest(copy)
    cell = resolve.find_workload(manifest, "gpt2-medium.b8-zero1")
    assert cell["chips"] == 4
    assert resolve.load_config(copy, manifest, cell["config"])["n_layer"] == 24
    traffic = resolve.load_traffic(bench, cell["traffic"])
    # the new file's keys laid over the one it extends
    assert traffic["per_chip_batch"] == 8 and traffic["seq_len"] == 1024
    assert traffic["family"] == "transformer_lm"
    assert "extends" not in traffic
    assert resolve.load_family(bench, "echo").build(1, 2) == (1, 2)
    names = [m["name"] for m in
             resolve.metrics_for(manifest, "per_layer", cell["name"])]
    assert "steps_seen" in names and "flash_roofline" not in names
    other = [m["name"] for m in resolve.metrics_for(
        manifest, "per_layer", "gpt2-small.b16-s1024")]
    assert "steps_seen" not in other and "flash_roofline" in other
    reader = resolve.load_layer_metric(bench, "steps_seen")
    assert reader.read({"rate": {"steps": 7}}) == 7

    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_a_reader_by_scope_and_by_kernel_name_is_a_file(copy):
    """A later PR's reader asks the traced operations by name: a scope
    segment of the ``op_name`` path, a prefix of a ``pallas_call`` name. Over
    the events recorded on the chip (PR 24's fixture, four devices) it gives
    what ``lib/scopes.split`` gives, and the kernels sum to ``flash_ms``."""
    import gzip

    from benchmark.lib import scopes, trace as T

    bench = os.path.join(copy, "benchmark")
    before = _digests(bench)
    with open(os.path.join(bench, "layer_metrics", "update_ms.py"), "w") as f:
        f.write("from benchmark.lib.by_name import scope_ms\n\n\n"
                "def read(run):\n    return scope_ms(run, 'hvd_update')\n")
    with open(os.path.join(bench, "layer_metrics", "dkv_ms.py"), "w") as f:
        f.write("from benchmark.lib.by_name import kernel_ms\n\n\n"
                "def read(run):\n"
                "    return kernel_ms(run, 'hvd_flash_bwd_dkv')\n")
    fixture = os.path.join(
        BENCH, "tests", "fixtures",
        "gpt2-small.b16-s1024.dp4.3steps.split.json.gz",
    )
    with gzip.open(fixture, "rt") as f:
        rec = json.load(f)
    names = dict(kernel_names=rec["kernel_names"],
                 collective_names=rec["collective_names"])
    run = {
        "trace": T.summarize(rec["events"], **names),
        "built": {"labels": rec["labels"],
                  "pallas_call_names": rec["kernel_names"]},
    }
    split = scopes.split(rec["events"], rec["labels"], mixed=rec["mixed"],
                         **names)["devices"]
    read = lambda name: resolve.load_layer_metric(  # noqa: E731
        bench, name
    ).read(run)
    assert read("update_ms") == pytest.approx(
        max(d["phases_ms"]["update"] for d in split), rel=1e-9
    )
    assert read("update_ms") == pytest.approx(3.44, abs=0.01)
    assert read("dkv_ms") == pytest.approx(
        max(d["kernels_ms"]["hvd_flash_bwd_dkv"] for d in split), rel=1e-9
    )
    # the committed readers: one file each, and together they are flash_ms
    parts = [read(f"flash_{k}_ms") for k in ("fwd", "bwd_dkv", "bwd_dq")]
    assert parts == pytest.approx([14.21, 14.04, 10.38], abs=0.01)
    assert sum(parts) == pytest.approx(read("flash_ms"), rel=1e-3)
    # nothing by that name: nothing to report, never a zero
    from benchmark.lib import by_name

    assert by_name.scope_ms(run, "update") is None  # whole segments only
    assert by_name.kernel_ms(run, "no_such_kernel") is None
    assert by_name.kernel_ms({**run, "trace": None}, "hvd_flash_fwd") is None
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_names_are_errors(copy):
    manifest = resolve.load_manifest(copy)
    with pytest.raises(resolve.NoSuchEntry):
        resolve.find_workload(manifest, "no-such-cell")
    with pytest.raises(resolve.NoSuchEntry):
        resolve.load_layer_metric(os.path.join(copy, "benchmark"), "nope")
    with pytest.raises(FileNotFoundError):
        resolve.load_traffic(os.path.join(copy, "benchmark"), "nope")


def test_step_kwargs_name_framework_objects_once_for_every_cell():
    class Compression:
        int8 = object()
        none = object()

    class Framework:
        pass

    Framework.Compression = Compression
    out = resolve.resolve_step_kwargs(
        {"compression": "int8", "gather_compression": "none",
         "sharded": True, "remat": "dots_saveable", "accum_steps": 2},
        Framework,
    )
    assert out["compression"] is Compression.int8
    assert out["gather_compression"] is Compression.none
    assert out["sharded"] is True and out["remat"] == "dots_saveable"
    assert out["accum_steps"] == 2
    with pytest.raises(AttributeError):
        resolve.resolve_step_kwargs({"compression": "int3"}, Framework)


def test_an_optimizer_and_its_schedule_are_data():
    import optax

    plain = resolve.resolve_optimizer(
        {"name": "adamw", "args": {"learning_rate": 3e-4}}, optax
    )
    assert isinstance(plain, optax.GradientTransformation)
    spec = resolve.load_traffic(BENCH, "cls-b96-s128-pad")["optimizer"]
    rate = spec["args"]["learning_rate"]
    schedule = getattr(optax, rate["schedule"])(**rate["args"])
    # linear warm-up from 0, as BERT's run_classifier.py does, then constant
    steps = rate["args"]["transition_steps"]
    assert float(schedule(0)) == 0.0
    assert float(schedule(steps // 2)) == pytest.approx(1e-5)
    assert float(schedule(steps)) == float(schedule(10 * steps)) == (
        pytest.approx(2e-5)
    )
    assert isinstance(
        resolve.resolve_optimizer(spec, optax), optax.GradientTransformation
    )
