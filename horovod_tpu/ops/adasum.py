"""Adasum: scale-invariant adaptive-sum reduction on the ICI torus.

TPU-native re-design of the reference's Adasum backend
(``horovod/common/ops/adasum/adasum.h`` — ``DispatchFusedAllreduce``
``:74-336``, pairwise projection math ``FusedPairwiseReduceWithComm``
``:338-398``). The math is identical; the execution is not: where the
reference runs recursive vector-halving distance-doubling over MPI
point-to-point sends, this implementation runs ``log2(n)`` rounds of
``lax.ppermute`` partner exchange inside the compiled SPMD program, letting
XLA schedule the ICI transfers.

Pairwise rule (reference ``adasum.h:386-396``): given the two partners'
vectors ``a`` (lower rank) and ``b`` (higher rank),

    adasum(a, b) = (1 - a·b / (2‖a‖²)) a + (1 - a·b / (2‖b‖²)) b

which subtracts the mean projected overlap, so parallel gradients average
while orthogonal gradients add. Applied over a binary tree: after round k,
every device holds the adasum of its 2^(k+1)-device block; after log2(n)
rounds all devices hold the full reduction.

Numerics: the reference accumulates dot/norms in fp64 (``adasum.h:352-359``)
— TPUs have no fp64 MXU path, so dot products here accumulate in fp32
(``jnp.vdot`` with ``preferred_element_type``), the documented TPU
translation in SURVEY.md §7.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..context import _axis_or_world
from ..exceptions import HorovodTpuError


def _pairwise(a: jax.Array, b: jax.Array) -> jax.Array:
    """One adasum combine; both partners compute the identical result."""
    af = a.astype(jnp.float32) if a.dtype != jnp.float32 else a
    bf = b.astype(jnp.float32) if b.dtype != jnp.float32 else b
    dot = jnp.vdot(af, bf)
    na = jnp.vdot(af, af)
    nb = jnp.vdot(bf, bf)
    # Guard zero-norm contributions (reference guards the same way by
    # skipping scaling when norms vanish).
    ca = jnp.where(na > 0, 1.0 - dot / (2.0 * na), 1.0)
    cb = jnp.where(nb > 0, 1.0 - dot / (2.0 * nb), 1.0)
    out = ca * af + cb * bf
    return out.astype(a.dtype)


def adasum_allreduce(tensor, axis=None):
    """Adasum-allreduce ``tensor`` over the world axis — any world size.

    Non-power-of-two worlds use the reference's VHDD remainder handling
    (``adasum.h:280-336``): with ``p`` the largest power of two ≤ n and
    ``r = n - p``, the first ``2r`` ranks pre-combine in adjacent pairs,
    the ``p`` survivors run the distance-doubling rounds, and the folded
    ranks receive the final result in a post-phase.
    """
    axes = _axis_or_world(axis)
    if len(axes) != 1:
        raise HorovodTpuError("adasum_allreduce expects a single flat axis")
    a = axes[0]
    try:
        n = int(lax.axis_size(a))
    except NameError as e:
        raise HorovodTpuError(
            f"adasum_allreduce requires mesh axis {a!r} to be bound — wrap "
            "your step with horovod_tpu.spmd(...)"
        ) from e

    p = 1 << (n.bit_length() - 1)  # largest power of two ≤ n
    r = n - p
    shape = tensor.shape
    x = jnp.ravel(tensor)
    idx = lax.axis_index(a)

    if r > 0:
        # Pre-phase: ranks (2i, 2i+1), i < r, exchange and combine; both
        # partners hold the pair's adasum, but only the even one stays
        # active for the doubling rounds.
        perm = [(2 * i, 2 * i + 1) for i in range(r)] + [
            (2 * i + 1, 2 * i) for i in range(r)
        ]
        other = lax.ppermute(x, a, perm)
        in_pair = idx < 2 * r
        is_lower = (idx % 2) == 0
        lo = jnp.where(is_lower, x, other)
        hi = jnp.where(is_lower, other, x)
        x = jnp.where(in_pair, _pairwise(lo, hi), x)

    # Virtual rank among the p active ranks: folded pairs contribute their
    # even member (virtual v → physical 2v for v < r), the unpaired tail
    # keeps its offset (physical v + r).
    def phys(v: int) -> int:
        return 2 * v if v < r else v + r

    vidx = jnp.where(idx < 2 * r, idx // 2, idx - r)
    active = jnp.where(idx < 2 * r, (idx % 2) == 0, True)
    level = 1
    while level < p:
        # Partner = virtual rank XOR level: the distance-doubling exchange
        # pattern of the reference's tree dispatch.
        perm = [(phys(v), phys(v ^ level)) for v in range(p)]
        other = lax.ppermute(x, a, perm)
        is_lower = (vidx & level) == 0
        lo = jnp.where(is_lower, x, other)
        hi = jnp.where(is_lower, other, x)
        x = jnp.where(active, _pairwise(lo, hi), x)
        level <<= 1

    if r > 0:
        # Post-phase: each pair's even rank hands the final value back to
        # its odd partner (reference's remainder broadcast-back).
        perm = [(2 * i, 2 * i + 1) for i in range(r)]
        from_active = lax.ppermute(x, a, perm)
        is_folded = (idx < 2 * r) & ((idx % 2) == 1)
        x = jnp.where(is_folded, from_active, x)
    return x.reshape(shape)


def adasum_allreduce_tree(tree, axis=None):
    """Adasum over a whole gradient pytree, per-leaf (the reference applies
    Adasum per fused buffer; per-leaf keeps each tensor scale-invariant
    independently, matching ``_DistributedAdasumOptimizer`` behavior)."""
    return jax.tree.map(lambda t: adasum_allreduce(t, axis=axis), tree)
