"""Milliseconds per step in the operations under the program's
``jax.named_scope("index_proj")``: the indexer's three projections, its
LayerNorm and rotary, their gradients (each weight's AdamW where XLA fuses
it into its gradient) and the select kernels' own glue. Device trace, worst
device; a fusion counts under the one scope its label names
(``lib/by_name.py``). Nothing to read in a program without the scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "index_proj")
