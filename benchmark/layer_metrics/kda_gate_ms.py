"""Milliseconds per step in the operations under the program's
``jax.named_scope("kda_gate")``: the Kimi Delta Attention layers' gates
(softplus and the decay's scale, beta's sigmoid, the gated head-wise
RMSNorm; each runs again in the backward from its low-rank input). Device
trace, worst device, forward, backward and what rematerialisation runs
again; a fusion counts under the one scope its label names
(``lib/by_name.py``). Nothing to read in a program without the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "kda_gate")
