"""Milliseconds per step in the operations under the program's
``jax.named_scope("moe_route")``: the router (fp32 scores over all experts, top-k, weights).
Device trace, worst device, forward, backward and what rematerialisation
runs again; a fusion counts under the one scope its label names
(``lib/by_name.py``). Nothing to read in a program without the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "moe_route")
