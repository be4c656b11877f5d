"""Expert parallelism: Switch-style top-1 MoE with all_to_all dispatch.

NEW capability relative to the reference (SURVEY.md §2.3: EP absent; the
reference's ``alltoall`` — ``operations.cc:1101-1162`` — was added for
exactly this use case). Each device on the ``ep`` axis owns one expert;
token routing is expressed as one-hot dispatch/combine einsums (large
MXU-friendly matmuls, the mesh-tensorflow formulation) around a pair of
``lax.all_to_all`` exchanges on the ICI.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax



def top1_dispatch(gate_logits, capacity: int):
    """Compute top-1 dispatch/combine tensors.

    Args: gate_logits ``[T, E]``; capacity per expert (this device's
    tokens only).
    Returns: dispatch ``[T, E, C]`` one-hot, combine ``[T, E, C]``
    (gate-prob weighted), aux_loss (Switch load-balancing loss).
    """
    t, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # [T]
    prob = jnp.max(probs, axis=-1)  # [T]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # [T, E]
    # Position of each token within its expert's queue.
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # [T, E]
    pos_of_token = jnp.sum(pos, axis=-1).astype(jnp.int32)  # [T]
    keep = pos_of_token < capacity
    onehot = onehot * keep[:, None]
    pos_onehot = jax.nn.one_hot(pos_of_token, capacity, dtype=jnp.float32)
    dispatch = onehot[:, :, None] * pos_onehot[:, None, :]  # [T, E, C]
    combine = dispatch * prob[:, None, None]
    # Switch aux loss: fraction of tokens * mean gate prob per expert.
    frac_tokens = jnp.mean(jax.nn.one_hot(expert, e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux_loss


def switch_moe(
    x,
    gate_kernel,
    expert_fn: Callable,
    expert_params,
    *,
    axis: str,
    capacity_factor: float = 1.25,
):
    """Top-1 MoE layer over the ``ep`` mesh axis, one expert per device.

    The ``e_local = 1`` case of :func:`switch_moe_stacked` (same routing,
    capacity, exchange layout, and aux loss — delegated so the two paths
    cannot diverge).

    Args:
      x: ``[T, D]`` this device's tokens.
      gate_kernel: ``[D, E]`` router weights (replicated).
      expert_fn: ``expert_fn(params, tokens) -> tokens`` applied to this
        device's expert batch ``[n*C, D]``.
      expert_params: THIS device's expert parameters (sharded over ``axis``).
      axis: expert-parallel mesh axis (E == axis size; one expert/device).
    Returns: ``([T, D] output, aux_loss)``.
    """

    def stacked_fn(params, toks):
        # toks [1, G, D] -> user fn on [G, D] -> [1, G, D]
        return expert_fn(params, toks[0])[None]

    return switch_moe_stacked(
        x,
        gate_kernel,
        stacked_fn,
        expert_params,
        axis=axis,
        capacity_factor=capacity_factor,
    )


def switch_moe_stacked(
    x,
    gate_kernel,
    expert_fn: Callable,
    local_expert_params,
    *,
    axis: str,
    capacity_factor: float = 1.25,
):
    """Top-1 MoE with ``e_local`` experts per device (GShard layout).

    Generalizes :func:`switch_moe`: ``E_total = n_devices * e_local``
    experts, device r owning experts ``r*e_local .. (r+1)*e_local-1``.

    Args:
      x: ``[T, D]`` this device's tokens.
      gate_kernel: ``[D, E_total]`` router weights (replicated).
      expert_fn: ``expert_fn(params, tokens) -> tokens`` applied with a
        leading stacked-expert axis: ``tokens [e_local, n*C, D]``.
      local_expert_params: THIS device's expert parameters, leaves stacked
        ``[e_local, ...]`` (the ``ep``-sharded shard of ``[E_total, ...]``).
    Returns: ``([T, D] output, aux_loss)``.
    """
    n = int(lax.axis_size(axis))
    t, d = x.shape
    e_total = gate_kernel.shape[-1]
    if e_total % n:
        raise ValueError(f"{e_total} experts not divisible by ep size {n}")
    e_local = e_total // n
    capacity = int(np.ceil(t / e_total * capacity_factor))

    gate_logits = x.astype(jnp.float32) @ gate_kernel.astype(jnp.float32)
    dispatch, combine, aux = top1_dispatch(gate_logits, capacity)

    # Bin per expert (device-major expert order), exchange device chunks.
    send = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=True)
    # recv[r*e_local + j] = source device r's bin for my local expert j.
    expert_in = (
        recv.reshape(n, e_local, capacity, d)
        .transpose(1, 0, 2, 3)
        .reshape(e_local, n * capacity, d)
    )
    expert_out = expert_fn(local_expert_params, expert_in)
    back = (
        expert_out.reshape(e_local, n, capacity, d)
        .transpose(1, 0, 2, 3)
        .reshape(e_total, capacity, d)
    )
    back = lax.all_to_all(back, axis, split_axis=0, concat_axis=0, tiled=True)
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), back)
    return out, lax.pmean(aux, axis)
