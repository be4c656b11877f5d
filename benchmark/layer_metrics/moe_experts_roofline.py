"""Share of their roofline the routed experts' matmuls reach: the least
time the chip could take for the three matmuls of ``E(x)`` forward and
backward over the EXPECTED rows ``T k held / experts`` of every expert
layer (``lib/flops_latent_moe.routed_expert_cost``) over
``moe_experts_ms``. The rows computed beyond the expected ones (the
program computes every held expert for every token, the bound that is
static: 32 times the expectation at top-8 of 256) and the matmuls it runs
again in the backward are in the time and not in the need, so they show as
a lower share. Nothing to read without a trace or in a
program without the ``moe_experts`` scope."""

from benchmark.lib.by_name import scope_ms
from benchmark.lib.flops import roofline
from benchmark.lib.flops_latent_moe import routed_expert_cost


def floor_seconds(run):
    config, traffic = run["cell"].config, run["cell"].traffic
    peak = run["peak"]
    if peak is None or "share" not in config:
        return None
    cost = routed_expert_cost(
        n_expert_layers=config["num_hidden_layers"]
        - config["first_k_dense_replace"]
        + config.get("num_nextn_predict_layers", 0),
        n_tokens=traffic["per_chip_batch"] * traffic["seq_len"],
        top_k=config["num_experts_per_tok"],
        n_held=config["n_routed_experts"],
        n_experts=config["share"]["router_width"],
        d_model=config["hidden_size"],
        d_expert=config["moe_intermediate_size"],
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
