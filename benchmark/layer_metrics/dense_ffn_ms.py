"""Milliseconds per step in the operations under the program's
``jax.named_scope("mlp")`` in a model whose every block has a dense FFN and
no expert layer: the gate, up and down matmuls of each block, forward and
backward, with each weight's AdamW where XLA fuses it into its gradient.
The same scope ``mlp_ms`` reads in the cells on its list; a name of its own
because that list is an accepted entry's. Device trace, worst device
(``lib/by_name.py``). Nothing to read in a program without the scope or in
a configuration with expert layers."""

from benchmark.lib.by_name import scope_ms


def read(run):
    if "layer_types" not in run["cell"].config:
        return None
    return scope_ms(run, "mlp")
