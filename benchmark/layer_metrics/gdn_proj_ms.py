"""Milliseconds per step in the operations under the program's
``jax.named_scope("gdn_proj")``: the Gated DeltaNet layers' projections (q,
k, v, z, o, the decay's ``a`` and beta's ``b``; each weight's AdamW where
XLA fuses it into its gradient). Device trace, worst device, forward,
backward and what rematerialisation runs again; a fusion counts under the
one scope its label names (``lib/by_name.py``). Nothing to read in a
program without the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "gdn_proj")
