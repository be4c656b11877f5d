"""Share of their roofline the select kernels reach: the least time the
chip could take for what they NEED (``lib/flops_select_moe.select_cost``:
the indexer's products over every CAUSAL pair, its operands read and the
int8 mask written once) over ``dsa_select_ms``. The ReLU, the weighted sum
over heads and the threshold's 32 rounds of compare-and-count are in the
time and not in the need, so the share reads low and no reading can pass
100%. Nothing to read without a trace, in a program that names no such
kernel or in a configuration without ``sa_config``."""

from benchmark.layer_metrics.dsa_select_ms import read as measured
from benchmark.lib import flops_select_moe as need


def floor_seconds(run):
    return need.floor_seconds(run, need.select_cost)


def read(run):
    return need.share_of_floor(run, need.select_cost, measured(run))
