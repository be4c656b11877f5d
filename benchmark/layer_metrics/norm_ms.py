"""Milliseconds per step in the operations under the program's
``jax.named_scope("norm")``: the blocks' LayerNorms / RMSNorms and residual
sums, the final norm and the norms before the heads; in the expert layer
also the sum of the routed and the shared experts' outputs. Device trace,
worst device, forward, backward and what rematerialisation runs again; a
fusion counts under the one scope its label names (``lib/by_name.py``;
``lib/parts.py`` has the whole cut of ``xla_ops_ms``). No Mosaic kernel lies
under it. Nothing to read in a program without the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "norm")
