"""Share of its roofline the Gated DeltaNet kernel family reaches: the
least time the chip could take for what the RECURRENCE needs, forward and
backward, in every Gated DeltaNet layer
(``lib/flops_linear_dense.gdn_cost``: ``18 d_k d_v`` FLOPs a position and
head; q, k, v, o, do, dq, dk, dv in the compute dtype and g, dg, beta,
dbeta, one a head, in float32, each once) over ``gdn_ms``. What a chunked
form adds (the pairwise terms inside a chunk, the triangle's inverse, what
the backward recomputes, the entry states it saves, the convolution at its
door and the norm at its exit) is in the time and not in the need, so it
shows as a lower share and no reading can pass 100%. Nothing to read
without a trace, in a program that names no ``hvd_gdn_*`` kernel or in a
configuration without ``linear_key_head_dim``."""

from benchmark.lib.by_name import kernel_ms
from benchmark.lib.flops import roofline


def floor_seconds(run):
    config, traffic = run["cell"].config, run["cell"].traffic
    peak = run["peak"]
    if peak is None or "linear_key_head_dim" not in config:
        return None
    from benchmark.lib.flops_linear_dense import LINEAR, gdn_cost, layer_kinds

    cost = gdn_cost(
        batch=traffic["per_chip_batch"], seq_len=traffic["seq_len"],
        n_heads=config["linear_num_value_heads"],
        d_k=config["linear_key_head_dim"],
        d_v=config["linear_value_head_dim"],
        layers=layer_kinds(config).count(LINEAR),
    )
    return roofline(
        cost["flops"], cost["bytes"], peak.bf16_flops, peak.hbm_bytes_per_s
    )["seconds"]


def read(run):
    measured_ms = kernel_ms(run, "hvd_gdn_")
    if not measured_ms:
        return None
    floor = floor_seconds(run)
    return None if floor is None else 100.0 * floor * 1e3 / measured_ms
