"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

NEW capability relative to the reference (SURVEY.md §2.3: PP absent). Each
device along the ``pp`` axis owns one stage's parameters; microbatches
stream through the ring via ``lax.ppermute`` (one hop per tick —
nearest-neighbor ICI traffic). The schedule runs ``M + n - 1`` ticks for
``M`` microbatches over ``n`` stages; autodiff through the schedule yields
the standard GPipe backward pipeline for free (``ppermute`` is
differentiable), so this composes with ``DistributedOptimizer`` over a
``dp`` axis.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax



def pipeline(
    stage_fn: Callable,
    stage_params,
    microbatches,
    *,
    axis: str,
):
    """Run ``microbatches`` through a ``stage_fn`` pipeline.

    Args:
      stage_fn: ``stage_fn(params, x) -> y``; applied by every device to
        whatever microbatch currently occupies its stage. All stages must
        map equal shapes (pad channels if needed).
      stage_params: this device's stage parameters (sharded over ``axis``
        outside — each device passes its own shard).
      microbatches: ``[M, ...]`` stacked microbatch inputs (replicated;
        only stage 0 consumes them).
      axis: the pipeline mesh axis.

    Returns: ``[M, ...]`` stacked stage-(n-1) outputs (valid on every
    device; non-final stages hold garbage copies of the same shape —
    callers typically read them on the last stage or rely on the returned
    value being correct ring-wide via the final collect permute).
    """
    n = int(lax.axis_size(axis))
    r = lax.axis_index(axis)
    m = microbatches.shape[0]
    x_shape = microbatches.shape[1:]

    state = jnp.zeros(x_shape, microbatches.dtype)  # stage input register
    outputs = jnp.zeros((m,) + x_shape, microbatches.dtype)

    fwd_perm = [(i, (i + 1) % n) for i in range(n)]

    for t in range(m + n - 1):
        # Stage 0 loads microbatch t (if any); other stages use what
        # arrived from the previous stage last tick.
        feed_idx = min(t, m - 1)
        inject = microbatches[feed_idx]
        x_in = jnp.where((r == 0) & (t < m), inject, state)
        y = stage_fn(stage_params, x_in)
        # The last stage's output for microbatch t-(n-1) is ready.
        out_idx = t - (n - 1)
        if out_idx >= 0:
            # Broadcast the final stage's result ring-wide so out_specs can
            # be replicated: psum of a masked contribution.
            contrib = jnp.where(r == n - 1, y, jnp.zeros_like(y))
            final = lax.psum(contrib, axis)
            outputs = outputs.at[out_idx].set(final)
        # Ship outputs one stage forward.
        state = lax.ppermute(y, axis, fwd_perm)

    return outputs
