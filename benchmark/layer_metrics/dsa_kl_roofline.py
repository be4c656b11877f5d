"""Share of their roofline the index-loss kernels reach: the least time
the chip could take for what they NEED (``lib/flops_select_moe.kl_cost``:
over the KEPT pairs one Q K^T a query head and the indexer's product once
forward and twice back, every operand read and every gradient written
once) over ``dsa_kl_ms``. The pairs a tile masks, the second indexer
product the backward half recomputes and the 32 exponentials a pair are in
the time and not in the need, so the share reads low and no reading can
pass 100%. Nothing to read without a trace, in a program that names no
such kernel or in a configuration without ``sa_config``."""

from benchmark.layer_metrics.dsa_kl_ms import read as measured
from benchmark.lib import flops_select_moe as need


def floor_seconds(run):
    return need.floor_seconds(run, need.kl_cost)


def read(run):
    return need.share_of_floor(run, need.kl_cost, measured(run))
