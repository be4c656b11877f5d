"""What a family file's ``build(config, traffic)`` returns."""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional


@dataclasses.dataclass
class Family:
    # key -> parameters, drawn on the device in one jitted call
    init_params: Callable
    # (params, batch) -> loss: the program's model, as a user writes it
    loss_fn: Callable
    # (params, batch) -> loss: plain jax.numpy, float32, no kernel
    reference_loss: Callable
    # params -> training FLOPs per batch position (lib/flops.py's rule)
    flops_per_token: Callable
    vocab_size: int
    # Shapes of the flash kernels one step runs for ONE sequence set of
    # ``batch`` per chip (keys of lib/flops.flash_attention_cost without
    # ``batch``), or None where the cell's attention bypasses them.
    flash: Optional[dict] = None


def matmul_params(params, lookup_tables) -> int:
    """Parameters that multiply every token: everything except the leaves
    under a top-level-or-deeper key in ``lookup_tables`` (see lib/flops.py
    for which tables those are)."""
    import jax

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = {getattr(p, "key", None) for p in path}
        if not keys & set(lookup_tables):
            total += int(leaf.size)
    return total
