"""The ``linear_dense_lm`` family at its files' ``tiny`` sizes on the CPU:
the whole of ``run.py``'s path rehearsed, the program (bf16) and its plain
reference (float32) agreeing, every control a real departure on one side,
the recurrence's FLOP and byte rule at the published sizes, every reader
this family's cell adds reading a hand-built record and returning None
where there is nothing to read, and the manifest's entries for the cell."""

import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import data, flops_linear_dense, harness, resolve
from benchmark.lib.peaks import PEAKS

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")
CELL = "Olmo-Hybrid-7B.lm-gdn-s8192"
READERS = ["gdn_ms", "gdn_fwd_ms", "gdn_bwd_ms", "gdn_roofline",
           "gdn_proj_ms", "gdn_gate_ms", "gdn_state_saved_gb",
           "mha_flash_ms", "mha_flash_roofline", "dense_ffn_ms"]


def _cell(tiny: bool):
    manifest = resolve.load_manifest(ROOT)
    w = resolve.find_workload(manifest, CELL)
    config = resolve.load_config(ROOT, manifest, w["config"])
    traffic = resolve.load_traffic(BENCH, w["traffic"])
    if tiny:
        config = {**config, **config["tiny"]}
        traffic = {**traffic, **traffic["tiny"]}
    return config, traffic


def _run(config, traffic, trace=None, peak=None, labels=None, kernels=()):
    """What a reader is handed (``lib/harness.measure``'s record)."""
    return {
        "trace": trace, "peak": peak,
        "built": {"labels": labels or {}, "pallas_call_names": list(kernels)},
        "cell": types.SimpleNamespace(config=config, traffic=traffic),
    }


def test_tiny_rehearsal_of_the_cell_is_correct(capsys):
    """``rehearse.py tiny``'s path: set-up, warm-up, a short window, the
    reference phase, the comparison and both result lines, on the CPU."""
    import time

    cell = harness.tiny(harness.load_cell(ROOT, BENCH, CELL))
    record = harness.measure(
        cell, bench_dir=BENCH, seed=3, seconds=0.5, traced=False,
        t_start=time.perf_counter(), rehearsal=True,
    )
    assert record["correct"], record["reasons"]
    assert record["attempted"] >= 2 and record["failed"] == 0
    metrics = harness.result_line(record, BENCH, traced=True)["metrics"]
    # untraced on the CPU: none of the cell's own readers has anything
    assert not set(metrics) & set(READERS)
    assert {"setup_s", "tokens_per_s_per_chip", "peak_hbm_gb"} <= set(
        harness.result_line(record, BENCH, traced=False)["metrics"]
    )


def test_tiny_family_agrees_with_its_plain_reference():
    config, traffic = _cell(tiny=True)
    family = resolve.load_family(BENCH, traffic["family"]).build(
        config, traffic
    )
    pool = data.make_pool(
        traffic["data"], vocab_size=family.vocab_size,
        global_batch=traffic["per_chip_batch"], seq_len=traffic["seq_len"],
        n_batches=1, seed=0,
    )
    assert pool[0]["tokens"].shape == (2, traffic["seq_len"] + 1)
    params = family.init_params(jax.random.PRNGKey(0))
    assert sorted(k for k in params if k.startswith("block_")) == [
        "block_0", "block_1", "block_2", "block_3"
    ]
    assert params["block_0"]["attn"]["q"]["kernel"].shape == (64, 2 * 12)
    assert params["block_0"]["attn"]["v"]["kernel"].shape == (64, 2 * 24)
    assert params["block_3"]["attn"]["q_norm"]["scale"].shape == (2 * 16,)
    ref_loss, ref_grad = jax.value_and_grad(family.reference_loss)(
        params, pool[0]
    )
    sys_loss, sys_grad = jax.value_and_grad(family.loss_fn)(params, pool[0])
    assert float(sys_loss) == pytest.approx(float(ref_loss), rel=5e-3)
    flat = lambda t: jnp.concatenate(  # noqa: E731
        [x.ravel().astype(jnp.float32) for x in jax.tree.leaves(t)]
    )
    a, b = flat(sys_grad), flat(ref_grad)
    cosine = float(a @ b / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
    assert cosine > 0.99, cosine
    assert family.flash is None and family.flops_per_token(params) > 0


def test_every_control_alters_the_reference_and_moves_the_loss():
    """``benchmark/controls.py`` runs these against the sound program: each
    is ONE departure of the reference and, at weights large enough for the
    mixers to matter, moves its loss."""
    config, traffic = _cell(tiny=True)
    module = resolve.load_family(BENCH, traffic["family"])
    controls = module.controls(config, traffic)
    sound = controls.pop("none")
    assert sorted(controls) == [
        "beta_without_its_factor_2", "convolution_skipped", "decay_dropped",
        "qk_norm_skipped", "reference_in_bfloat16",
    ]
    pool = data.make_pool(
        traffic["data"], vocab_size=sound.vocab_size,
        global_batch=traffic["per_chip_batch"], seq_len=traffic["seq_len"],
        n_batches=1, seed=1,
    )
    params = sound.init_params(jax.random.PRNGKey(1))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 8 if x.ndim > 1 and "conv" not in str(path[-1])
        else x, params,
    )
    was = float(sound.reference_loss(params, pool[0]))
    for name, family in controls.items():
        assert family.loss_fn is sound.loss_fn, name
        assert family.reference_loss is not sound.reference_loss, name
        assert float(family.reference_loss(params, pool[0])) != was, name


def test_cost_rules_at_the_published_sizes():
    config, traffic = _cell(tiny=False)
    assert flops_linear_dense.layer_kinds(config) == (
        ["linear_attention"] * 3 + ["full_attention"]
    )
    cost = flops_linear_dense.gdn_cost(
        batch=1, seq_len=8192, n_heads=15, d_k=96, d_v=192, layers=1,
    )
    # 18 d_k d_v a position and head; q k dq dk at 96, v o do dv at 192 in
    # bf16, g dg beta dbeta one float32 a head
    assert cost["flops"] == 18 * 96 * 192 * 8192 * 15 == 40_768_634_880
    assert cost["bytes"] == 8192 * 15 * ((4 * 96 + 4 * 192) * 2 + 16)
    three = flops_linear_dense.gdn_cost(
        batch=1, seq_len=8192, n_heads=15, d_k=96, d_v=192, layers=3,
    )
    assert three == {k: 3 * v for k, v in cost.items()}
    per_token = flops_linear_dense.train_flops_per_token(
        n_matmul_params=1000, n_linear_layers=3, n_full_layers=1,
        seq_len=8192, n_heads=15, head_dim=128, d_k=96, d_v=192,
    )
    assert per_token == (
        6 * 1000 + 12 * 8192 * 15 * 128 + 3 * 18 * 15 * 96 * 192
    )
    with pytest.raises(ValueError, match="layer_types"):
        flops_linear_dense.layer_kinds({**config, "layer_types": ["x"] * 4})


def test_configuration_states_its_share_and_every_published_width():
    config, traffic = _cell(tiny=False)
    heads = ["num_attention_heads", "num_key_value_heads",
             "linear_num_key_heads", "linear_num_value_heads"]
    assert config["reduced"] == ["num_hidden_layers", *heads, "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 32, **{k: 30 for k in heads},
        "vocab_size": 100352,
    }
    share = config["share"]
    assert share["chips_per_layer"] * share["heads_held"] == 30
    assert all(config[k] == share["heads_held"] == 15 for k in heads)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["rms_norm_eps"],
            config["linear_allow_neg_eigval"],
            config["max_position_embeddings"]) == (
        3840, 11008, 96, 192, 4, 1e-6, True, 65536)
    assert config["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]
    ) * 8
    assert config["rope_parameters"] == {"rope_theta": None}
    sizes = resolve.load_family(BENCH, traffic["family"]).sizes(config)
    assert (sizes["n_heads"], sizes["heads_held"], sizes["first_head"],
            sizes["head_dim"], sizes["gdn_key_dim"], sizes["gdn_value_dim"],
            sizes["d_ff"], sizes["n_layers"]) == (
        30, 15, 0, 128, 96, 192, 11008, 4)
    assert "766,241,946" in config["parameters"]
    assert traffic["seq_len"] == 8192 and traffic["per_chip_batch"] == 1
    assert traffic["expect"]["pallas_calls"] == 9
    assert traffic["reference"]["loss_rel_tol"] <= 1e-2


def test_manifest_names_the_cell_its_configuration_and_its_metrics():
    manifest = resolve.load_manifest(ROOT)
    entry = [c for c in manifest["configs"] if c["name"] == "Olmo-Hybrid-7B"]
    assert len(entry) == 1 and entry[0]["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    )
    cell = resolve.find_workload(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "Olmo-Hybrid-7B", "lm-gdn-b1-s8192", 1)
    listed = {m["name"]: m for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert sorted(listed) == sorted(READERS)
    assert all(m["workloads"] == [CELL] for m in listed.values())
    assert listed["gdn_state_saved_gb"]["moves"] == "peak_hbm_gb"
    assert listed["gdn_roofline"]["unit"] == "%"
    # the new cell is the manifest's last, and one cell in nine takes four
    assert manifest["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


@pytest.mark.parametrize("name", READERS)
def test_new_readers_find_nothing_without_a_trace(name):
    """No trace, a program without the scopes, the kernels' names or the
    counters (as the parent is), or another family's cell: the reader
    returns None and does not raise."""
    config, traffic = _cell(tiny=False)
    reader = resolve.load_layer_metric(BENCH, name)
    assert reader.read(_run(config, traffic)) is None
    manifest = resolve.load_manifest(ROOT)
    other = manifest["workloads"][0]
    run = _run(resolve.load_config(ROOT, manifest, other["config"]),
               resolve.load_traffic(BENCH, other["traffic"]))
    assert reader.read(run) is None


def _traced(op_seconds, steps=10):
    return types.SimpleNamespace(
        devices=[types.SimpleNamespace(op_seconds=op_seconds)], steps=steps,
    )


def test_new_readers_read_a_traced_run():
    """A hand-built record: the two kernel families told apart by their
    names, the scopes read by segment, the shares the floors over those
    times."""
    config, traffic = _cell(tiny=False)
    grad = "jit(step)/hvd_grad/"
    labels = {
        "call.1": grad + "LinearDenseLM/block_0/attn/hvd_gdn_fwd",
        "call.2": grad + "transpose(jvp(block_0))/attn/hvd_gdn_bwd",
        "call.3": grad + "LinearDenseLM/block_3/attn/hvd_flash_fwd",
        "call.4": grad + "transpose(jvp(block_3))/attn/hvd_flash_bwd_dq",
        "fusion.1": grad + "block_0/attn/gdn_proj/dot_general",
        "fusion.2": grad + "block_0/attn/gdn_gate/mul",
        "fusion.3": grad + "block_0/ffn/mlp/dot_general",
        "fusion.4": grad + "block_0/norm/add",
    }
    seconds = {"call.1": 0.08, "call.2": 0.17, "call.3": 0.03,
               "call.4": 0.07, "fusion.1": 0.3, "fusion.2": 0.02,
               "fusion.3": 1.9, "fusion.4": 7.0}
    run = _run(config, traffic, trace=_traced(seconds),
               peak=PEAKS["TPU v5 lite"], labels=labels,
               kernels=["call.1", "call.2", "call.3", "call.4"])
    read = lambda name: resolve.load_layer_metric(  # noqa: E731
        BENCH, name
    ).read(run)
    assert read("gdn_fwd_ms") == pytest.approx(8.0)
    assert read("gdn_bwd_ms") == pytest.approx(17.0)
    assert read("gdn_ms") == pytest.approx(25.0)
    assert read("mha_flash_ms") == pytest.approx(10.0)
    assert read("gdn_proj_ms") == pytest.approx(30.0)
    assert read("gdn_gate_ms") == pytest.approx(2.0)
    assert read("dense_ffn_ms") == pytest.approx(190.0)
    peak = PEAKS["TPU v5 lite"]
    # three layers' 0.855 GB at the HBM peak bound the recurrence's floor
    floor_ms = 3 * 8192 * 15 * 2320 / peak.hbm_bytes_per_s * 1e3
    assert read("gdn_roofline") == pytest.approx(100 * floor_ms / 25.0)
    assert 0 < read("gdn_roofline") < 100
    # 15 causal heads of 128 over 8,192: 7 matmuls of 2 s (s + 1) / 2 d
    flash_ms = 15 * 7 * 2 * (8192 * 8193 / 2) * 128 / peak.bf16_flops * 1e3
    assert read("mha_flash_roofline") == pytest.approx(
        100 * flash_ms / 10.0, rel=1e-6
    )


def test_state_saved_reads_the_programs_counters():
    """``gdn_state_saved_gb``: bytes a forward kernel built leaves, times
    the step's ``hvd_gdn_*`` calls; None where the step holds none."""
    from horovod_tpu.obs import registry
    from horovod_tpu.ops.kda_kernels import kda_attention

    config, traffic = _cell(tiny=False)
    reader = resolve.load_layer_metric(BENCH, "gdn_state_saved_gb")
    reg = registry.always()
    before = (reg.counter("gdn.state_bytes_saved").get(),
              reg.counter("gdn.calls").get())
    shape = lambda w, dt: jax.ShapeDtypeStruct((1, 256, w), dt)  # noqa: E731
    jax.eval_shape(
        lambda q, v, g: kda_attention(q, q, v, g, g, n_heads=2,
                                      use_kernel=True),
        shape(2 * 96, jnp.bfloat16), shape(2 * 192, jnp.bfloat16),
        shape(2, jnp.float32),
    )
    saved = reg.counter("gdn.state_bytes_saved").get() - before[0]
    assert saved == 2 * (256 // 64) * 192 * 96 * 2
    assert reg.counter("gdn.calls").get() - before[1] == 1
    labels = {"call.1": "jit(step)/hvd_grad/block_0/attn/hvd_gdn_fwd",
              "call.2": "jit(step)/hvd_grad/block_3/attn/hvd_flash_fwd"}
    run = _run(config, traffic, labels=labels, kernels=["call.1", "call.2"])
    total = reg.counter("gdn.state_bytes_saved").get()
    calls = reg.counter("gdn.calls").get()
    assert reader.read(run) == pytest.approx(total / calls / 1e9)
    assert reader.read(_run(config, traffic, labels=labels,
                            kernels=["call.2"])) is None
