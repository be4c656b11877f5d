"""The ``window_moe_lm`` family at its files' ``tiny`` sizes on the CPU:
the program (bf16) and its plain reference (float32) agree, the band's
FLOP and byte rules give what a brute-force count gives, every reader this
family's cell adds reads a fixture and returns None where there is no
trace to read."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import data, flops_window_moe, resolve
from benchmark.lib.peaks import PEAKS

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")
CELL = "SmallThinker-21BA3B-Instruct.lm-s16384"
TRACE_READERS = ["gqa_flash_roofline", "window_flash_ms", "full_flash_ms",
                 "pre_route_ms", "reglu_experts_ms", "reglu_experts_roofline"]


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
    assert pool[0]["tokens"].max() < config["vocab_size"]
    params = family.init_params(jax.random.PRNGKey(0))
    assert sorted(k for k in params if k.startswith("block_")) == [
        "block_0", "block_1", "block_2", "block_3"
    ]
    assert params["block_1"]["experts_gate"].shape == (4, 48, 24)
    assert params["block_1"]["router"].shape == (48, 16)
    assert params["block_1"]["attn"]["k"]["kernel"].shape == (48, 2 * 16)
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


@pytest.mark.parametrize("name", TRACE_READERS)
def test_new_readers_find_nothing_without_a_trace(name):
    """No trace (an untraced run, or a program without the scopes or the
    kernels' names): the reader returns None and does not raise."""
    config, traffic = _cell(tiny=False)
    reader = resolve.load_layer_metric(BENCH, name)
    assert reader.read(_run(config, traffic)) is None
    # another family's cell: nothing of this family's in its files
    manifest = resolve.load_manifest(ROOT)
    other = manifest["workloads"][0]
    run = _run(resolve.load_config(ROOT, manifest, other["config"]),
               resolve.load_traffic(BENCH, other["traffic"]))
    assert reader.read(run) is None


def _traced(op_seconds, steps=10):
    """A trace of one device whose operations took ``op_seconds``."""
    kernels_s = sum(t for name, t in op_seconds.items() if "call" in name)
    return types.SimpleNamespace(
        devices=[types.SimpleNamespace(op_seconds=op_seconds)], steps=steps,
        per_step_ms=lambda key: {"kernels_s": kernels_s}[key] / steps * 1e3,
    )


def test_new_readers_read_a_traced_run():
    """A hand-built record: window and full kernels told apart by the end
    of their names and summing to the kernels' time, the two scopes read by
    segment, the two shares the floors over those times."""
    config, traffic = _cell(tiny=False)
    labels = {
        "call.1": "jit(step)/hvd_grad/WindowMoELM/block_0/attn/hvd_flash_fwd",
        "call.2": "jit(step)/hvd_grad/block_1/attn/hvd_flash_fwd_window",
        "call.3": "jit(step)/hvd_grad/transpose(jvp(block_1))/attn/"
                  "hvd_flash_bwd_dkv_window",
        "call.4": "jit(step)/hvd_grad/transpose(jvp(block_0))/attn/"
                  "hvd_flash_bwd_dq",
        "fusion.1": "jit(step)/hvd_grad/block_1/moe_route/dot_general",
        "fusion.2": "jit(step)/hvd_grad/block_1/moe_experts/dot_general",
        "fusion.3": "jit(step)/hvd_grad/block_1/norm/add",
    }
    seconds = {"call.1": 0.4, "call.2": 0.2, "call.3": 0.5, "call.4": 0.9,
               "fusion.1": 0.01, "fusion.2": 1.5, "fusion.3": 7.0}
    run = _run(config, traffic, trace=_traced(seconds),
               peak=PEAKS["TPU v5 lite"], labels=labels,
               kernels=["call.1", "call.2", "call.3", "call.4"])
    read = lambda name: resolve.load_layer_metric(  # noqa: E731
        BENCH, name
    ).read(run)
    assert read("window_flash_ms") == pytest.approx(70.0)
    assert read("full_flash_ms") == pytest.approx(130.0)
    assert read("pre_route_ms") == pytest.approx(1.0)
    assert read("reglu_experts_ms") == pytest.approx(150.0)
    # 15.57 TFLOP needed: 79.0 ms at 197 TFLOP/s, over 200 ms of kernels
    assert read("gqa_flash_roofline") == pytest.approx(39.52, rel=1e-3)
    # 3 x 3 matmuls over 12,288 rows of 2560 x 768 in 4 layers: 8.8 ms
    assert read("reglu_experts_roofline") == pytest.approx(5.886, rel=1e-3)


def test_skipped_share_reads_the_programs_counters():
    """``flash_tiles_skipped_share``: the share of the counters the kernels
    booked when they were built; None in a process that built no causal
    call (it does not raise where the program lacks the counters)."""
    from horovod_tpu.obs import registry
    from horovod_tpu.ops.pallas_kernels import flash_attention

    reader = resolve.load_layer_metric(BENCH, "flash_tiles_skipped_share")
    config, traffic = _cell(tiny=False)
    reg = registry.always()
    before = [reg.counter(f"flash.tiles.{k}").get()
              for k in ("visited", "skipped")]
    if not sum(before):
        assert reader.read(_run(config, traffic)) is None
    x = jax.ShapeDtypeStruct((1, 2048, 2 * 128), jnp.bfloat16)
    jax.eval_shape(lambda q: flash_attention(
        q, q, q, causal=True, window=512, layout="bsm", n_heads=2,
    ), x)
    visited, skipped = (
        reg.counter(f"flash.tiles.{k}").get() for k in ("visited", "skipped")
    )
    assert (visited - before[0], skipped - before[1]) == (21, 43)
    assert reader.read(_run(config, traffic)) == pytest.approx(
        100.0 * skipped / (visited + skipped)
    )


@pytest.mark.parametrize("s,window", [(64, 16), (64, 5), (40, 64), (33, 1)])
def test_band_counts_against_brute_force(s, window):
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    full = int(((i - j) >= 0).sum())
    band = int((((i - j) >= 0) & ((i - j) < window)).sum())
    assert flops_window_moe.valid_entries(s) == full
    assert flops_window_moe.valid_entries(s, window) == band
    cost = flops_window_moe.window_flash_cost(
        windows=[None, window, window], batch=2, n_heads=6, n_kv_heads=2,
        seq_len=s, head_dim=16,
    )
    assert cost["entries"] == full + 2 * band
    # every valid entry costs a query head 2 matmuls forward and 5
    # backward, 2 d FLOPs each
    assert cost["flops"] == 2 * 6 * (full + 2 * band) * 7 * 2 * 16
    # q, o / q, o, do, dq at 6 heads; k, v / k, v, dk, dv at 2; lse twice
    assert cost["bytes"] == 2 * 3 * (
        6 * (6 * s * 16 * 2 + 2 * s * 4) + 2 * 6 * s * 16 * 2
    )


def test_cost_rules_at_the_published_sizes():
    config, traffic = _cell(tiny=False)
    s = traffic["seq_len"]
    assert flops_window_moe.valid_entries(s, 4096) == 58_722_304
    assert flops_window_moe.valid_entries(s) == 134_225_920
    windows = [None, 4096, 4096, 4096]
    flash = flops_window_moe.window_flash_cost(
        windows=windows, batch=1, n_heads=28, n_kv_heads=4, seq_len=s,
        head_dim=128,
    )
    assert flash["flops"] == pytest.approx(15.57e12, rel=1e-3)
    experts = flops_window_moe.routed_expert_cost(
        n_expert_layers=4, n_tokens=s, top_k=6, n_held=8, n_experts=64,
        d_model=2560, d_expert=768,
    )
    assert experts["rows"] == 12288
    per_token = flops_window_moe.train_flops_per_token(
        n_always_params=0, n_expert_params=3 * 2560 * 768, n_layers=4,
        top_k=6, n_held=8, n_experts=64, windows=windows, seq_len=s,
        n_heads=28, head_dim=128,
    )
    assert per_token == (
        6 * 4 * 0.75 * 3 * 2560 * 768 + 12 * (s + 3 * 4096) * 28 * 128
    )


def test_configuration_states_its_share():
    config, traffic = _cell(tiny=False)
    assert config["reduced"] == ["moe_num_primary_experts", "vocab_size",
                                 "num_hidden_layers"]
    assert config["published"] == {"moe_num_primary_experts": 64,
                                   "vocab_size": 151936,
                                   "num_hidden_layers": 52}
    share = config["share"]
    assert share["router_width"] == (
        share["chips_per_layer"] * config["moe_num_primary_experts"]
    )
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # every published width, and both layouts whole
    assert (config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"],
            config["sliding_window_size"], config["rope_theta"],
            config["max_position_embeddings"]) == (
        2560, 128, 28, 4, 768, 6, 4096, 1500000, 16384)
    assert config["rope_layout"] == config["sliding_window_layout"] == (
        [0, 1, 1, 1] * 13
    )
    assert traffic["seq_len"] == config["max_position_embeddings"]
    module = resolve.load_family(BENCH, traffic["family"])
    sizes = module.sizes(config)
    assert sizes["window_layout"] == sizes["rope_layout"] == (0, 1, 1, 1)
    assert flops_window_moe.layer_windows(config) == [None, 4096, 4096,
                                                      4096]
    manifest = resolve.load_manifest(ROOT)
    entry = [c for c in manifest["configs"]
             if c["name"] == "SmallThinker-21BA3B-Instruct"][0]
    assert entry["reduced"] == config["reduced"]


def test_every_control_alters_one_side_and_moves_the_loss():
    """``benchmark/controls.py`` runs these against the sound other side:
    each alters the program or the reference, never both, and at the tiny
    sizes each moves its side's loss."""
    config, traffic = _cell(tiny=True)
    module = resolve.load_family(BENCH, traffic["family"])
    controls = module.controls(config, traffic)
    sound = controls.pop("none")
    assert sorted(controls) == ["reference_in_bfloat16",
                                "router_reads_ffn_norm", "window_ignored"]
    pool = data.make_pool(
        traffic["data"], vocab_size=sound.vocab_size,
        global_batch=traffic["per_chip_batch"], seq_len=traffic["seq_len"],
        n_batches=1, seed=1,
    )
    params = sound.init_params(jax.random.PRNGKey(1))
    # weights large enough that the masks and the routing move the loss
    params = jax.tree.map(lambda x: x * 8 if x.ndim > 1 else x, params)
    for name, family in controls.items():
        altered_program = family.loss_fn is not sound.loss_fn
        altered_reference = family.reference_loss is not sound.reference_loss
        assert altered_program != altered_reference, name
        side = "loss_fn" if altered_program else "reference_loss"
        was = float(getattr(sound, side)(params, pool[0]))
        now = float(getattr(family, side)(params, pool[0]))
        assert now != was, name
    from horovod_tpu.parallel import ep

    assert ep.topk_route.__module__ == ep.local_experts.__module__ == ep.__name__
