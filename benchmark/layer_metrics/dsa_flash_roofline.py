"""Share of their roofline the masked flash kernels reach: the least time
the chip could take for what they NEED
(``lib/flops_select_moe.masked_flash_cost``: a grouped-query layer's
forward and backward over the KEPT pairs, q / o / do / dq at the query
heads, k / v / dk / dv at the K/V heads, the mask read once a kernel) over
``dsa_flash_ms``. Tiles a kernel visits and masks are in the time and not
in the need: with the kept set scattered over every tile the kernels do a
causal layer's work for 43.75% of its entries, so the share reads low and
no reading can pass 100%. Nothing to read without a trace, in a program
that names no such kernel or in a configuration without ``sa_config``."""

from benchmark.layer_metrics.dsa_flash_ms import read as measured
from benchmark.lib import flops_select_moe as need


def floor_seconds(run):
    return need.floor_seconds(run, need.masked_flash_cost)


def read(run):
    return need.share_of_floor(run, need.masked_flash_cost, measured(run))
