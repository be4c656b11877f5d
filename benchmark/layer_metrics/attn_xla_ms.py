"""Milliseconds per step in the operations under the program's
``jax.named_scope("attn_xla")``: scores, mask, softmax and ``P V`` where the
flash kernels are bypassed (a padding mask sends attention down the XLA
path). Device trace, worst device, forward, backward and what
rematerialisation runs again; a fusion counts under the one scope its label
names (``lib/by_name.py``; ``lib/parts.py`` has the whole cut of
``xla_ops_ms``). No Mosaic kernel lies under it. Nothing to read in a
program without the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "attn_xla")
