"""chip_smoke.py on the CPU: its phases at ``GPT2Config.tiny()`` with the
chip checks relaxed HERE (a monkeypatch inside the child, never a CLI flag of
the script), the unmodified script refusing a CPU-only host, and the
compile-cache helper's placement rule."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Runs chip_smoke.run() with the two checks only a chip can pass replaced:
# the device must be a TPU, and the compiled step must hold Mosaic kernels
# (off-TPU the auto choice is XLA attention / the interpreter, by design).
_RELAXED = """
import sys
import chip_smoke
from horovod_tpu.models.gpt2 import GPT2Config
chip_smoke.check_device = lambda dev: None
chip_smoke.check_pallas_calls = lambda hlo, what: 0
device = chip_smoke.run(int(sys.argv[1]), 0, GPT2Config.tiny())
assert "horovod_tpu.native" not in sys.modules, "smoke loaded the native lib"
chip_smoke.emit(ok=True, device=device)
"""

_STEP_PHASE_KEYS = {
    "phase", "model", "world", "global_batch", "seq", "lower_s", "compile_s",
    "compile", "compiled_memory", "compiles_inside_steps", "tpu_custom_calls",
    "collectives", "losses", "step_ms_smoke_reading", "peak_bytes_in_use",
}
_TINY = {
    "n_layers": 2, "d_model": 64, "n_heads": 4, "d_ff": 128,
    "vocab_size": 512, "max_len": 128, "dtype": "bfloat16",
}


def _run(args, n_devices, tmp_path, script=None):
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
    }
    cmd = (
        [sys.executable, "-c", script] if script
        else [sys.executable, os.path.join(REPO, "chip_smoke.py")]
    )
    out = subprocess.run(
        cmd + args, env=env, cwd=REPO, capture_output=True, text=True,
        timeout=280,
    )
    lines = [json.loads(l) for l in out.stdout.splitlines()]  # all JSON
    return out, lines


def _check_step_phase(line, world, global_batch, steps):
    assert set(line) == _STEP_PHASE_KEYS, set(line) ^ _STEP_PHASE_KEYS
    assert {k: line["model"][k] for k in _TINY} == _TINY
    assert line["model"]["n_params"] > 0
    assert (line["world"], line["global_batch"], line["seq"]) == (
        world, global_batch, 128,
    )
    assert len(line["losses"]) == len(line["step_ms_smoke_reading"]) == steps
    assert line["losses"][-1] < line["losses"][0]
    assert len(line["peak_bytes_in_use"]) == world
    for side in ("requested", "compiled"):
        assert set(line["collectives"][side]) == {
            "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute",
        }
    assert set(line["compiled_memory"]) == {
        "argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
    }
    for stats in (line["compile"], line["compiles_inside_steps"]):
        assert set(stats) == {"compile_requests", "cache_hits", "cache_misses"}


def test_one_chip_phases_at_tiny(tmp_path):
    out, lines = _run(["1"], 1, tmp_path, script=_RELAXED)
    assert out.returncode == 0, out.stderr[-3000:]
    by_phase = {l["phase"]: l for l in lines[:-1]}
    assert [l["phase"] for l in lines[:-1]] == [
        "env", "init", "fused_adamw_kernel", "dp.make_train_step",
        "hvd.spmd+DistributedOptimizer", "agreement",
    ]
    env = by_phase["env"]
    assert set(env) == {
        "phase", "chips", "seed", "jax", "jaxlib", "libtpu", "device",
        "peak_tflops_bf16", "compile_cache_dir",
    }
    assert env["jax"] == jax.__version__
    assert env["compile_cache_dir"] == str(tmp_path / "cache")
    assert by_phase["init"]["log"].startswith("hvd.init(): found 1 cpu")
    assert by_phase["init"]["size"] == 1
    adamw = by_phase["fused_adamw_kernel"]
    assert set(adamw["max_abs_diff"]) == {"update", "m", "v"}
    assert max(adamw["diff_over_max_value"].values()) <= adamw["tolerance"]
    for name in ("dp.make_train_step", "hvd.spmd+DistributedOptimizer"):
        _check_step_phase(by_phase[name], 1, 16, 4)
    agreement = by_phase["agreement"]
    assert agreement["spmd_vs_dp_final_loss_rel_diff"] <= agreement[
        "tolerance_rel"
    ]
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


def test_four_chip_phases_at_tiny(tmp_path):
    out, lines = _run(["4"], 4, tmp_path, script=_RELAXED)
    assert out.returncode == 0, out.stderr[-3000:]
    by_phase = {l["phase"]: l for l in lines[:-1]}
    assert [l["phase"] for l in lines[:-1]] == [
        "env", "reference_1dev", "init", "dp4", "zero1", "zero1_state",
        "agreement", "dp4_full_shape",
    ]
    assert by_phase["init"]["size"] == 4
    _check_step_phase(by_phase["reference_1dev"], 1, 16, 4)
    _check_step_phase(by_phase["dp4"], 4, 16, 4)
    _check_step_phase(by_phase["zero1"], 4, 16, 4)
    _check_step_phase(by_phase["dp4_full_shape"], 4, 64, 3)
    assert by_phase["dp4"]["collectives"]["compiled"]["all-reduce"] >= 1
    zero1 = by_phase["zero1"]["collectives"]["requested"]
    assert zero1["reduce-scatter"] >= 1 and zero1["all-gather"] >= 1
    shards = by_phase["zero1_state"]
    assert shards["n_buckets"] == len(shards["bucket_elems"]) > 0
    assert [4 * s for s in shards["shard_elems"]] == shards["bucket_elems"]
    agreement = by_phase["agreement"]
    for leg in ("dp4", "zero1"):
        assert agreement[f"{leg}_vs_reference_final_loss_rel_diff"] <= (
            agreement["tolerance_rel"]
        )
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }


def test_one_chip_mode_does_not_spread_over_a_larger_host(tmp_path):
    out, lines = _run(["1"], 4, tmp_path, script=_RELAXED)
    assert out.returncode != 0
    assert [l["phase"] for l in lines] == ["env"]  # nothing ran
    assert "exactly 1 device" in out.stderr


@pytest.mark.parametrize("args", [[], ["--chips", "4"]], ids=["1", "4"])
def test_unmodified_script_refuses_a_cpu_host(tmp_path, args):
    out, lines = _run(args, 4, tmp_path)
    assert out.returncode != 0
    assert len(lines) == 1, lines  # no phase line, no result
    assert lines[0]["ok"] is False
    assert "no TPU" in lines[0]["reason"]


def test_compile_cache_helper_placement(monkeypatch):
    from horovod_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    names_in_key = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable_compile_cache() == "/some/dir"
        # an executable from the cache carries its first compiler's names
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        # JAX reads the variable itself; nothing was set in code.
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()  # fixed path
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", names_in_key
        )


@pytest.mark.parametrize("names_in_key", [False, True])
def test_a_scope_alone_moves_the_cache_key_only_with_names_in_it(
    names_in_key
):
    """Why ``enable_compile_cache`` puts the metadata in the key: two
    programs that differ in a ``jax.named_scope`` alone share JAX's default
    key, so the second would be handed the first one's executable, names
    and all; with the metadata in the key each has its own."""
    import jax.numpy as jnp
    import numpy as np
    from jax._src import cache_key, compiler

    def key_of(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) + 1

        device = jax.devices()[0]
        return cache_key.get(
            jax.jit(f).lower(jnp.ones((4,))).compiler_ir(),
            np.array([device]), compiler.get_compile_options(1, 1),
            device.client,
        )

    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update(
        "jax_compilation_cache_include_metadata_in_key", names_in_key
    )
    try:
        assert (key_of("embed") != key_of("norm")) == names_in_key
    finally:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", was
        )
