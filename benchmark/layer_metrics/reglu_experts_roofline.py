"""Share of their roofline the ReLU-gated routed experts' matmuls reach:
the least time the chip could take for the three matmuls of ``E(x)``
forward and backward over the EXPECTED rows ``T k held / experts`` of every
layer (``lib/flops_window_moe.routed_expert_cost``) over
``reglu_experts_ms``. The rows computed beyond the expected ones (the
program computes every held expert for every token: 10.7 times the
expectation at top-6 of 64) and the matmuls it runs again in the backward
are in the time and not in the need, so they show as a lower share.
Nothing to read without a trace, in a program without the ``moe_experts``
scope or in a configuration of another family."""

from benchmark.lib.by_name import scope_ms
from benchmark.lib.flops import roofline
from benchmark.lib.flops_window_moe import routed_expert_cost


def floor_seconds(run):
    config, traffic = run["cell"].config, run["cell"].traffic
    peak = run["peak"]
    if peak is None or "moe_num_primary_experts" not in config:
        return None
    cost = routed_expert_cost(
        n_expert_layers=config["num_hidden_layers"],
        n_tokens=traffic["per_chip_batch"] * traffic["seq_len"],
        top_k=config["moe_num_active_primary_experts"],
        n_held=config["moe_num_primary_experts"],
        n_experts=config["share"]["router_width"],
        d_model=config["hidden_size"],
        d_expert=config["moe_ffn_hidden_size"],
    )
    return roofline(
        cost["flops"], cost["bytes"], peak.bf16_flops, peak.hbm_bytes_per_s
    )["seconds"]


def read(run):
    measured_ms = scope_ms(run, "moe_experts")
    if not measured_ms:
        return None
    floor = floor_seconds(run)
    return None if floor is None else 100.0 * floor * 1e3 / measured_ms
