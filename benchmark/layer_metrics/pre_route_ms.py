"""Milliseconds per step in the operations under the program's
``jax.named_scope("moe_route")`` where the router stands ahead of the
attention and reads its input: fp32 scores over all experts at full
matmul precision, top-k of the logits, the softmax over the chosen.
Device trace, worst device, forward, backward and what rematerialisation
runs again (``lib/by_name.py``). Nothing to read in a program without the
scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "moe_route")
