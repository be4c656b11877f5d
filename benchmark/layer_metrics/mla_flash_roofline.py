"""Share of their roofline the flash kernels reach at the latent
attention's two head widths: the least time the chip could take for the
operations and bytes the three passes need
(``lib/flops_latent_moe.latent_flash_cost``: q / k heads ``qk_head_dim``
wide, v / out heads ``v_head_dim``, mask counted, every attention block,
the multi-token module's among them) over ``flash_ms``, which also holds
the forward that rematerialisation runs again. Nothing to read without a
trace or in a configuration without the two widths."""

from benchmark.lib.flops import roofline
from benchmark.lib.flops_latent_moe import latent_flash_cost


def floor_seconds(run):
    config, traffic = run["cell"].config, run["cell"].traffic
    peak = run["peak"]
    if peak is None or "qk_head_dim" not in config:
        return None
    cost = latent_flash_cost(
        n_blocks=config["num_hidden_layers"]
        + config.get("num_nextn_predict_layers", 0),
        batch=traffic["per_chip_batch"],
        n_heads=config["num_attention_heads"], seq_len=traffic["seq_len"],
        qk_dim=config["qk_head_dim"], v_dim=config["v_head_dim"],
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
