"""Share of their roofline the flash kernels reach in the full-attention
layers of a configuration that names its layers' kinds: the least time the
chip could take for the operations and bytes the three passes need
(``lib/flops.flash_attention_cost``: the heads held, ``hidden_size`` over
the heads the model is parametrised with wide, causal, mask counted, every
``full_attention`` layer) over ``mha_flash_ms`` (the ``hvd_flash_*``
kernels only). Nothing to read without a trace, in a program that names no
such kernel or in a configuration without ``layer_types``."""

from benchmark.lib.by_name import kernel_ms
from benchmark.lib.flops import flash_attention_cost, roofline


def floor_seconds(run):
    config, traffic = run["cell"].config, run["cell"].traffic
    peak = run["peak"]
    if peak is None or "layer_types" not in config:
        return None
    from benchmark.lib.flops_linear_dense import FULL, layer_kinds

    held = config["num_attention_heads"]
    cost = flash_attention_cost(
        n_layers=layer_kinds(config).count(FULL),
        batch=traffic["per_chip_batch"], n_heads=held,
        seq_len=traffic["seq_len"],
        head_dim=config["hidden_size"]
        // (config["share"]["chips_per_layer"] * held),
        causal=True,
    )
    return roofline(
        cost["flops"], cost["bytes"], peak.bf16_flops, peak.hbm_bytes_per_s
    )["seconds"]


def read(run):
    measured_ms = kernel_ms(run, "hvd_flash_")
    if not measured_ms:
        return None
    floor = floor_seconds(run)
    return None if floor is None else 100.0 * floor * 1e3 / measured_ms
