"""The ``select_moe_lm`` family at its files' ``tiny`` sizes on the CPU:
the program (bf16) and its plain reference (float32) agree, the select
family's FLOP and byte rules give what hand counts give, every reader this
family's cell adds reads a fabricated record and returns None where there
is nothing to read."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import data, flops_select_moe, resolve
from benchmark.lib.peaks import PEAKS

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")
CELL = "Keye-VL-2.0-30B-A3B.lm-dsa-s8192"
TRACE_READERS = ["dsa_select_ms", "dsa_select_roofline", "dsa_flash_ms",
                 "dsa_flash_roofline", "dsa_kl_ms", "dsa_kl_roofline",
                 "dsa_index_proj_ms"]
COUNTER_READERS = ["dsa_kept_share", "dsa_mask_gb"]


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


def _flat(tree):
    return jnp.concatenate(
        [x.ravel().astype(jnp.float32) for x in jax.tree.leaves(tree)]
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
        "block_0", "block_1"
    ]
    attn = params["block_1"]["attn"]
    assert attn["index_q"]["kernel"].shape == (48, 4 * 8)
    assert attn["index_k"]["kernel"].shape == (48, 8)
    assert attn["index_w"].shape == (48, 4)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert params["block_1"]["router"].shape == (48, 16)
    ref_loss, ref_grad = jax.value_and_grad(family.reference_loss)(
        params, pool[0]
    )
    sys_loss, sys_grad = jax.value_and_grad(family.loss_fn)(params, pool[0])
    assert float(sys_loss) == pytest.approx(float(ref_loss), rel=5e-3)
    a, b = _flat(sys_grad), _flat(ref_grad)
    cosine = float(a @ b / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
    assert cosine > 0.99, cosine
    assert family.flash is None and family.flops_per_token(params) > 0


@pytest.mark.parametrize("name", TRACE_READERS + COUNTER_READERS)
def test_new_readers_find_nothing_where_nothing_is(name):
    """No trace and no select kernel in the step (an untraced run, a
    program older than the family): the reader returns None and does not
    raise; ``dsa_kept_share`` needs the program's counters alone."""
    config, traffic = _cell(tiny=False)
    reader = resolve.load_layer_metric(BENCH, name)
    if name != "dsa_kept_share":
        assert reader.read(_run(config, traffic)) is None
    # another family's cell: nothing of this family's in its files
    manifest = resolve.load_manifest(ROOT)
    other = manifest["workloads"][0]
    run = _run(resolve.load_config(ROOT, manifest, other["config"]),
               resolve.load_traffic(BENCH, other["traffic"]))
    if name != "dsa_kept_share":
        assert reader.read(run) is None
    else:
        assert reader.read(run) in (None, pytest.approx(
            reader.read(_run(config, traffic))
        ))


def _traced(op_seconds, steps=10):
    """A trace of one device whose operations took ``op_seconds``."""
    return types.SimpleNamespace(
        devices=[types.SimpleNamespace(op_seconds=op_seconds)], steps=steps,
    )


def _fabricated(seconds):
    config, traffic = _cell(tiny=False)
    labels = {
        "call.1": "jit(step)/hvd_grad/block_0/attn/hvd_dsa_select",
        "call.2": "jit(step)/hvd_grad/block_0/attn/hvd_flash_fwd_select",
        "call.3": "jit(step)/hvd_grad/transpose(jvp(block_0))/attn/"
                  "hvd_flash_bwd_dkv_select",
        "call.4": "jit(step)/hvd_grad/transpose(jvp(block_0))/attn/"
                  "hvd_flash_bwd_dq_select",
        "call.5": "jit(step)/hvd_grad/block_0/attn/hvd_dsa_kl",
        "call.6": "jit(step)/hvd_grad/block_0/attn/hvd_flash_fwd",
        "fusion.1": "jit(step)/hvd_grad/block_0/attn/index_proj/dot_general",
        "fusion.2": "jit(step)/hvd_grad/block_0/attn/attn_proj/dot_general",
    }
    return _run(config, traffic, trace=_traced(seconds),
                peak=PEAKS["TPU v5 lite"], labels=labels,
                kernels=[f"call.{i}" for i in range(1, 7)])


def test_new_readers_read_a_traced_run():
    """A hand-built record: each kernel group by its names (a flash kernel
    without ``_select`` is no part of ``dsa_flash_ms``), the scope by its
    segment, each share its need over its time."""
    run = _fabricated({
        "call.1": 0.3, "call.2": 0.5, "call.3": 0.9, "call.4": 0.6,
        "call.5": 0.4, "call.6": 5.0, "fusion.1": 0.02, "fusion.2": 0.7,
    })
    read = lambda name: resolve.load_layer_metric(  # noqa: E731
        BENCH, name
    ).read(run)
    assert read("dsa_select_ms") == pytest.approx(30.0)
    assert read("dsa_flash_ms") == pytest.approx(200.0)
    assert read("dsa_kl_ms") == pytest.approx(40.0)
    assert read("dsa_index_proj_ms") == pytest.approx(2.0)
    # 6 layers x 2 x 16 x 64 x 33,558,528 = 0.412 TFLOP: 2.09 ms at 197
    assert read("dsa_select_roofline") == pytest.approx(6.977, rel=1e-3)
    # 6 x 32 x 14,681,088 x 14 x 128 = 5.051 TFLOP: 25.64 ms
    assert read("dsa_flash_roofline") == pytest.approx(12.82, rel=1e-3)
    # 6 x 14,681,088 x (2 x 32 x 128 + 6 x 16 x 64) = 1.263 TFLOP: 6.41 ms
    assert read("dsa_kl_roofline") == pytest.approx(16.03, rel=1e-3)


@pytest.mark.parametrize("name", ["dsa_select_roofline", "dsa_flash_roofline",
                                  "dsa_kl_roofline"])
def test_a_roofline_never_passes_100_at_the_floor(name):
    """Kernels that took exactly their floor read 100; any real time is
    longer, so the share stays under it."""
    reader = resolve.load_layer_metric(BENCH, name)
    probe = _fabricated({f"call.{i}": 1.0 for i in range(1, 7)})
    floor = reader.floor_seconds(probe)
    n = {"dsa_select_roofline": ["call.1"], "dsa_kl_roofline": ["call.5"],
         "dsa_flash_roofline": ["call.2", "call.3", "call.4"]}[name]
    at_floor = _fabricated({k: floor * 10 / len(n) for k in n})
    assert reader.read(at_floor) == pytest.approx(100.0)
    slower = _fabricated({k: floor * 10 / len(n) * 1.7 for k in n})
    assert 0 < reader.read(slower) < 100


def test_counter_readers_read_the_programs_counters():
    """``dsa_kept_share`` and ``dsa_mask_gb`` from what ``dsa_select``
    booked when a call was built, at the cell's length and ``topk``."""
    from horovod_tpu.obs import registry
    from horovod_tpu.ops.dsa_kernels import dsa_select

    config, traffic = _cell(tiny=False)
    reg = registry.always()
    names = ("dsa.calls", "dsa.entries.causal", "dsa.entries.kept",
             "dsa.mask_bytes")
    before = [reg.counter(n).get() for n in names]
    s = traffic["seq_len"]
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)  # noqa: E731
    jax.eval_shape(
        lambda q, k, w: dsa_select(q, k, w, top_k=2048, use_kernel=True),
        shape(1, s, 16 * 64), shape(1, s, 64), shape(1, s, 16),
    )
    booked = [reg.counter(n).get() - b for n, b in zip(names, before)]
    assert booked == [1, 33_558_528, 14_681_088, s * s]
    if not sum(before):  # only this call in the process: the cell's share
        share = resolve.load_layer_metric(BENCH, "dsa_kept_share")
        assert share.read(_run(config, traffic)) == pytest.approx(
            100 * 14_681_088 / 33_558_528
        )
    mask = resolve.load_layer_metric(BENCH, "dsa_mask_gb")
    labels = {f"call.{i}": f"jit(step)/block_{i}/attn/hvd_dsa_select"
              for i in range(6)}
    run = _run(config, traffic, labels=labels, kernels=list(labels))
    per_call = reg.counter("dsa.mask_bytes").get() / reg.counter(
        "dsa.calls").get()
    assert mask.read(run) == pytest.approx(6 * per_call / 1e9)
    assert mask.read(_run(config, traffic)) is None  # no such kernel


@pytest.mark.parametrize("s,topk", [(64, 16), (40, 64), (33, 1), (96, 96)])
def test_entry_counts_against_brute_force(s, topk):
    rows = np.arange(s) + 1
    assert flops_select_moe.causal_entries(s) == int(rows.sum())
    assert flops_select_moe.kept_entries(s, topk) == int(
        np.minimum(rows, topk).sum()
    )
    from horovod_tpu.ops.dsa_kernels import kept_entries

    assert kept_entries(s, topk) == flops_select_moe.kept_entries(s, topk)


def test_cost_rules_against_hand_counts():
    z = dict(layers=3, batch=2, seq_len=64, topk=16, n_heads=6, n_kv_heads=2,
             head_dim=16, index_heads=4, index_head_dim=8)
    causal, kept = 64 * 65 // 2, 16 * 17 // 2 + 48 * 16
    select = flops_select_moe.select_cost(**z)
    assert select["flops"] == 6 * 2 * 4 * 8 * causal
    # qI (4 heads) and kI (1) of 8 in bf16, w fp32, the mask, tau and lse_I
    assert select["bytes"] == 6 * (64 * 5 * 8 * 2 + 64 * 4 * 4 + 64 * 64
                                   + 2 * 64 * 4)
    flash = flops_select_moe.masked_flash_cost(**z)
    assert flash["entries"] == kept
    assert flash["flops"] == 6 * 6 * kept * 7 * 2 * 16
    assert flash["bytes"] == 6 * (
        6 * (6 * 64 * 16 * 2 + 2 * 64 * 4) + 2 * 6 * 64 * 16 * 2
        + 3 * 64 * 64
    )
    kl = flops_select_moe.kl_cost(**z)
    assert kl["flops"] == 6 * kept * (2 * 6 * 16 + 3 * 2 * 4 * 8)
    index_bytes = 64 * 5 * 8 * 2 + 64 * 4 * 4
    assert kl["bytes"] == 6 * (64 * 8 * 16 * 2 + 6 * 64 * 4 + 64 * 4
                               + 64 * 64 + 2 * index_bytes)


def test_cost_rules_at_the_published_sizes():
    config, traffic = _cell(tiny=False)
    z = flops_select_moe.shapes(config, traffic)
    assert (z["seq_len"], z["topk"], z["layers"]) == (8192, 2048, 6)
    assert flops_select_moe.causal_entries(8192) == 33_558_528
    assert flops_select_moe.kept_entries(8192, 2048) == 14_681_088
    assert flops_select_moe.masked_flash_cost(**z)["flops"] == pytest.approx(
        5.051e12, rel=1e-3
    )
    per_token = flops_select_moe.train_flops_per_token(
        n_always_params=0, n_expert_params=3 * 2048 * 768, n_layers=6,
        top_k=8, n_held=8, n_experts=128, seq_len=8192, topk=2048,
        n_heads=32, head_dim=128, index_heads=16, index_head_dim=64,
    )
    c_kept, c_causal = 14_681_088 / 8192, 33_558_528 / 8192
    assert per_token == pytest.approx(
        6 * 6 * 0.5 * 3 * 2048 * 768 + 6 * (
            12 * c_kept * 32 * 128 + 2 * c_causal * 1024
            + c_kept * (2 * 32 * 128 + 6 * 1024)
        )
    )


def test_configuration_states_its_share():
    config, traffic = _cell(tiny=False)
    assert config["reduced"] == ["num_experts", "num_local_experts",
                                 "vocab_size", "num_hidden_layers"]
    assert config["published"] == {
        "num_experts": 128, "num_local_experts": 128, "vocab_size": 151936,
        "num_hidden_layers": 48,
    }
    share = config["share"]
    assert (share["chips_per_layer"], share["chip"],
            share["router_width"]) == (16, 0, 128)
    assert share["router_width"] == (
        share["chips_per_layer"] * config["num_experts"]
    )
    assert config["num_experts"] == config["num_local_experts"] == 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # every published width, the indexer and the rotary copied whole
    assert (config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["intermediate_size"], config["rope_theta"],
            config["max_position_embeddings"]) == (
        2048, 128, 32, 4, 768, 8, 6144, 10000000, 262144)
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048,
    }
    assert config["rope_scaling"] == {
        "mrope_section": [16, 24, 24], "rope_type": "default",
        "type": "default",
    }
    for item in ("qk_norm", "rope", "indexer", "selection", "index_loss",
                 "chunk_sizes", "vision_tower", "initializer_range",
                 "dtypes"):
        assert item in config["assumed"], item
    assert "432.7 M" in config["parameters"]
    module = resolve.load_family(BENCH, traffic["family"])
    sizes = module.sizes(config)
    assert (sizes["index_top_k"], sizes["index_heads"],
            sizes["index_head_dim"], sizes["index_blocks"]) == (
        2048, 16, 64, (512, 512))
    assert (sizes["router_input"], sizes["expert_activation"],
            sizes["qk_norm"]) == ("ffn_norm", "silu", True)
    manifest = resolve.load_manifest(ROOT)
    entry = [c for c in manifest["configs"]
             if c["name"] == "Keye-VL-2.0-30B-A3B"][0]
    assert entry["reduced"] == config["reduced"]
    per_layer = [m for m in manifest["per_layer"]
                 if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in per_layer) == sorted(
        TRACE_READERS + COUNTER_READERS
    )


def test_parameter_count_is_the_files():
    """432.7 M at the published widths, counted from shapes alone."""
    config, traffic = _cell(tiny=False)
    family = resolve.load_family(BENCH, traffic["family"]).build(
        config, traffic
    )
    shapes = jax.eval_shape(family.init_params, jax.random.PRNGKey(0))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert round(total / 1e6, 1) == 432.7
    attn = shapes["block_0"]["attn"]
    indexer = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        {k: v for k, v in attn.items() if k.startswith("index_")}
    ))
    assert round(indexer / 1e6, 2) == 2.26


def test_every_control_alters_one_side_and_moves_the_loss():
    """``benchmark/controls.py`` runs these against the sound other side:
    each alters the program or the reference, never both, and at the tiny
    sizes each moves its side's loss."""
    config, traffic = _cell(tiny=True)
    module = resolve.load_family(BENCH, traffic["family"])
    controls = module.controls(config, traffic)
    sound = controls.pop("none")
    assert sorted(controls) == ["index_loss_left_out",
                                "reference_in_bfloat16", "selection_ignored"]
    pool = data.make_pool(
        traffic["data"], vocab_size=sound.vocab_size,
        global_batch=traffic["per_chip_batch"], seq_len=traffic["seq_len"],
        n_batches=1, seed=1,
    )
    params = sound.init_params(jax.random.PRNGKey(1))
    # weights large enough that the selection moves the loss
    params = jax.tree.map(lambda x: x * 8 if x.ndim > 1 else x, params)
    for name, family in controls.items():
        altered_program = family.loss_fn is not sound.loss_fn
        altered_reference = family.reference_loss is not sound.reference_loss
        assert altered_program != altered_reference, name
        side = "loss_fn" if altered_program else "reference_loss"
        was = float(getattr(sound, side)(params, pool[0]))
        now = float(getattr(family, side)(params, pool[0]))
        assert now != was, name
