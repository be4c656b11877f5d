"""The ``latent_moe_lm`` family at its files' ``tiny`` sizes on the CPU:
the program (bf16) and its plain reference (float32) agree, the FLOP and
byte rules give what their docstrings work out, and every reader this
family's cell adds returns None where there is no trace to read."""

import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import data, flops_latent_moe, resolve

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")
CELL = "JoyAI-LLM-Flash.mtp-s4096"
TRACE_READERS = ["mla_proj_ms", "moe_route_ms", "moe_experts_ms", "mtp_ms", "mla_flash_roofline",
                 "moe_experts_roofline", "mla_flash_fwd_ms",
                 "mla_flash_bwd_dkv_ms", "mla_flash_bwd_dq_ms"]


def _cell(tiny: bool):
    manifest = resolve.load_manifest(ROOT)
    w = resolve.find_workload(manifest, CELL)
    config = resolve.load_config(ROOT, manifest, w["config"])
    traffic = resolve.load_traffic(BENCH, w["traffic"])
    if tiny:
        config = {**config, **config["tiny"]}
        traffic = {**traffic, **traffic["tiny"]}
    return config, traffic


def _run(config, traffic, trace=None):
    """What a reader is handed (``lib/harness.measure``'s record)."""
    return {
        "trace": trace, "peak": None,
        "built": {"labels": {}, "pallas_call_names": []},
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
    assert pool[0]["tokens"].shape == (2, traffic["seq_len"] + 2)
    assert pool[0]["tokens"].max() < config["vocab_size"]
    params = family.init_params(jax.random.PRNGKey(0))
    assert params["block_1"]["ffn"]["experts_gate"].shape == (8, 64, 24)
    assert params["block_1"]["ffn"]["router"].shape == (64, 32)
    assert "router" not in params["block_0"]["ffn"]  # the leading dense one
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
    """No trace (an untraced run, or a program without the scopes): the
    reader returns None and does not raise."""
    config, traffic = _cell(tiny=False)
    reader = resolve.load_layer_metric(BENCH, name)
    assert reader.read(_run(config, traffic)) is None
    # another family's cell: nothing of this family's in its files
    manifest = resolve.load_manifest(ROOT)
    other = manifest["workloads"][0]
    run = _run(resolve.load_config(ROOT, manifest, other["config"]),
               resolve.load_traffic(BENCH, other["traffic"]))
    assert reader.read(run) is None


def test_cost_rules_at_the_published_sizes():
    config, traffic = _cell(tiny=False)
    s = traffic["seq_len"]
    flash = flops_latent_moe.latent_flash_cost(
        n_blocks=1, batch=1, n_heads=1, seq_len=s, qk_dim=192, v_dim=128,
    )
    e = s * (s + 1) / 2
    assert flash["flops"] == 2 * e * (320 + 832)
    assert flash["bytes"] == (6 * 192 + 6 * 128) * s * 2 + 2 * s * 4
    experts = flops_latent_moe.routed_expert_cost(
        n_expert_layers=5, n_tokens=2 * s, top_k=8, n_held=16,
        n_experts=256, d_model=2048, d_expert=768,
    )
    assert experts["rows"] == 4096
    assert experts["flops"] == 5 * 9 * 2 * 4096 * 2048 * 768
    per_token = flops_latent_moe.train_flops_per_token(
        n_always_params=0, n_expert_params=3 * 2048 * 768,
        n_expert_layers=5, top_k=8, n_held=16, n_experts=256,
        n_attention_blocks=6, seq_len=s, n_heads=32, qk_dim=192, v_dim=128,
    )
    assert per_token == 6 * 5 * 0.5 * 3 * 2048 * 768 + 6 * 6 * s * 32 * 320


def test_configuration_states_its_share():
    config, _ = _cell(tiny=False)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    share = config["share"]
    assert share["router_width"] == (
        share["chips_per_layer"] * config["n_routed_experts"]
    )
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["qk_head_dim"] == (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    )


def test_every_control_alters_one_side_and_moves_the_loss():
    """``benchmark/controls.py`` runs these against the sound other side:
    each alters the program or the reference, never both, and at the tiny
    sizes each moves its side's loss."""
    config, traffic = _cell(tiny=True)
    module = resolve.load_family(BENCH, traffic["family"])
    controls = module.controls(config, traffic)
    sound = controls.pop("none")
    assert sorted(controls) == ["reference_in_bfloat16", "router_in_bfloat16",
                                "shared_expert_left_out"]
    pool = data.make_pool(
        traffic["data"], vocab_size=sound.vocab_size,
        global_batch=traffic["per_chip_batch"], seq_len=traffic["seq_len"],
        n_batches=1, seed=1,
    )
    params = sound.init_params(jax.random.PRNGKey(1))
    for name, family in controls.items():
        altered_program = family.loss_fn is not sound.loss_fn
        altered_reference = family.reference_loss is not sound.reference_loss
        assert altered_program != altered_reference, name
        side = "loss_fn" if altered_program else "reference_loss"
        was = float(getattr(sound, side)(params, pool[0]))
        now = float(getattr(family, side)(params, pool[0]))
        assert now != was, name
