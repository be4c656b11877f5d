"""Hierarchical (ICI/DCN two-level) allreduce.

TPU-native equivalent of ``NCCLHierarchicalAllreduce``
(``horovod/common/ops/nccl_operations.cc:292-364``): intra-node
reduce-scatter → cross-node allreduce on the shard → intra-node
all-gather. On TPU the levels are the ICI torus (``local`` axis, one pod
slice) and DCN (``cross`` axis, across slices); the cross-level transfer
shrinks by a factor of ``local_size`` exactly as in the reference.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..context import _traced_size
from ..ops.collectives import Average, ReduceOp, Sum


def hierarchical_allreduce(
    x,
    *,
    local_axis: str = "local",
    cross_axis: str = "cross",
    op: ReduceOp = Average,
):
    """reduce_scatter(ICI) → psum(DCN) → all_gather(ICI).

    Equivalent to ``psum(x, (cross, local))`` but structured so the DCN hop
    moves ``1/local_size`` of the bytes. Works on any shape (internally
    flattened and padded to a multiple of the local axis size).
    """
    nl = int(lax.axis_size(local_axis))
    world = _traced_size((local_axis, cross_axis))
    shape, dtype = x.shape, x.dtype
    flat = jnp.ravel(x)
    size = flat.shape[0]
    padded = -(-size // nl) * nl
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    shard = lax.psum_scatter(flat, local_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, cross_axis)
    full = lax.all_gather(shard, local_axis, axis=0, tiled=True)
    if padded != size:
        full = full[:size]
    out = full.reshape(shape)
    if op == Average:
        if jnp.issubdtype(dtype, jnp.integer):
            out = out // world
        else:
            out = out / world
    elif op != Sum:
        raise ValueError("hierarchical_allreduce supports Sum/Average")
    return out
