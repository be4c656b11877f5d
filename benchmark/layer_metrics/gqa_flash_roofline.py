"""Share of their roofline the flash kernels reach under a band and with
grouped heads: the least time the chip could take for the operations and
bytes the three passes need (``lib/flops_window_moe.window_flash_cost``:
the entries each layer's own mask leaves valid, q / o / do at the query
heads and k / v / dk / dv at the K/V heads) over ``flash_ms``, which also
holds a forward that rematerialisation runs again. Tiles outside the band
are not in the need, so skipping them cannot read over 100% and masking
them without skipping reads low. Nothing to read without a trace or in a
configuration without a window layout."""

from benchmark.lib.flops import roofline
from benchmark.lib.flops_window_moe import layer_windows, window_flash_cost


def floor_seconds(run):
    config, traffic = run["cell"].config, run["cell"].traffic
    peak = run["peak"]
    if peak is None or "sliding_window_layout" not in config:
        return None
    cost = window_flash_cost(
        windows=layer_windows(config), batch=traffic["per_chip_batch"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        seq_len=traffic["seq_len"], head_dim=config["head_dim"],
    )
    return roofline(
        cost["flops"], cost["bytes"], peak.bf16_flops, peak.hbm_bytes_per_s
    )["seconds"]


def read(run):
    t = run["trace"]
    if t is None:
        return None
    floor = floor_seconds(run)
    measured_ms = t.per_step_ms("kernels_s")
    if floor is None or measured_ms <= 0:
        return None
    return 100.0 * floor * 1e3 / measured_ms
