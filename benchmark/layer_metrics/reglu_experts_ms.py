"""Milliseconds per step in the operations under the program's
``jax.named_scope("moe_experts")`` where the held experts are ReLU-gated:
the three matmuls over every held expert and every token (the bound),
forward, backward and the two the backward runs again. Device trace,
worst device (``lib/by_name.py``). Nothing to read in a program without
the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "moe_experts")
