"""Milliseconds per step in the operations under the program's
``jax.named_scope("moe_experts")``: the held experts' matmuls (each
token's weights for the held experts, gate, up, down over every held
expert and token).
Device trace, worst device, forward, backward and what rematerialisation
runs again; a fusion counts under the one scope its label names
(``lib/by_name.py``). Nothing to read in a program without the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "moe_experts")
