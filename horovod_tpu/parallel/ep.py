"""Expert parallelism: Switch-style top-1 MoE with all_to_all dispatch, and
top-k routing over all experts computed for the experts held here.

NEW capability relative to the reference (SURVEY.md §2.3: EP absent; the
reference's ``alltoall`` — ``operations.cc:1101-1162`` — was added for
exactly this use case). Each device on the ``ep`` axis owns one expert;
token routing is expressed as one-hot dispatch/combine einsums (large
MXU-friendly matmuls, the mesh-tensorflow formulation) around a pair of
``lax.all_to_all`` exchanges on the ICI.

:func:`topk_route` and :func:`local_experts` are the other half: the
router scores every expert of a layer that is shared by several chips, and
the chip computes the part of the result that ITS experts give, for the
tokens routed to them, dropping none. On one chip there is no exchange;
where tokens of other chips arrive (the exchange of a four-chip mesh) they
join ``x`` before :func:`local_experts` and leave after it.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import registry as _registry



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


# ---------------------------------------------------------------------------
# Top-k routing over all experts; the experts held here compute their part.
#
# The device work is a function of (tokens, experts held) alone, never of
# where the router sends what, and no token is dropped whatever it does. A
# token chooses ``k`` DIFFERENT experts, so an expert held here receives at
# most every token once: ``T`` rows. That bound is the buffer. Expert ``e``'s
# segment is ``T`` rows and token ``t`` sits at row ``t`` of it, so there is
# no slot to find, no sort, no cumulative sum, no gather and no scatter: the
# three matmuls run over all ``held x T`` rows, a row's output is weighed by
# the router's weight for that (token, expert) and by zero where the token
# did not choose the expert, and the weighing is folded in before the down
# projection, which then sums over experts as well: one contraction over
# ``(held, F)``.
#
# The form of the dots (PERF.md, PR 39). The stacks are stored ``[held, D,
# F]`` (gate, up) and ``[held, F, D]`` (down): the plain reference reads the
# same tree, so the shapes stay. Gate and up contract ``D`` with the expert
# axis as a BATCH dimension of the dot, the tokens broadcast over it
# (``etd,edf->etf``): each dot's free dimension is ``F`` alone and
# ``[held, D, F]`` is the operand as it is stored. Written as one dot whose
# free dimension is ``(held, F)`` merged (``td,edf->tef``) the contracted
# ``D`` lies between the two in memory; the TPU's compiler then wants the
# array ``[held][F][D]``, carries that layout through the cast to the
# parameter and through the fused AdamW to both moments, and copies all
# three round on entry and back on exit, every step: 60 copies of
# ``f32[16,2048,768]``, a seventeenth of the expert cell's step. The hidden
# rows are expert-major (``[held, T, F]``) and the down projection
# (``etf,efd->td``) merges ``(held, F)`` as ``down`` is stored.
#
# The backward of the two batched dots is written out (``_gate_up``'s
# ``custom_vjp``), because what autodiff derives from them costs more than
# the copies did. dx stays what it was in the merged form, ONE contraction
# over ``(held, F)`` a dot (``etf,edf->td``: the expert axis a contracted
# window of the convolution, float32 accumulation, rounded once); derived,
# it is a batched dot that writes the ``[held, T, D]`` partial products to
# HBM (537 MB a dot in the expert cell) and a sum over rounded partials.
# dW has to be batched over the experts to come out as the stacks are
# stored, and every batched dW reads the tokens TRANSPOSED (33.5 MB); with
# one dW for gate and one for up the compiler keeps that array in fast
# memory for the first and reads it from HBM for the second, which then
# takes twice its time. So gate's and up's dW are ONE batched dot over the
# two cotangents side by side (``[held, T, 2F]``), cut in two afterwards.
#
# Why not a smaller buffer (``slack`` x the expected ``T k held / experts``
# rows, overflow passes under ``lax.cond``)? It was built and measured
# (PERF.md, PR 36): under AdamW at a constant 3e-4 the hidden state of
# every token collapses onto one vector within ten steps, every token then
# chooses the same ``k`` experts, and the rows an expert-parallel chip
# receives swing between 0 and ``k T`` from one step to the next, with or
# without a balance update of the score bias (a bias cannot tell apart
# tokens whose scores are the same). Only the bound is static.
# ---------------------------------------------------------------------------


def topk_route(x, router_kernel, score_bias, *, top_k: int,
               scale: float = 1.0, scoring: str = "sigmoid"):
    """Top-k routing over ALL experts of the layer, the scores in float32
    at full matmul precision.

    ``scoring="sigmoid"`` (``noaux_tc``): ``s = sigmoid(x W_r)``; the
    ``top_k`` largest of ``s + score_bias`` are chosen (the bias steers the
    choice only and takes no gradient); their weights are ``s_sel /
    sum(s_sel) * scale``.  ``scoring="softmax"``: the ``top_k`` largest of
    the logits ``x W_r`` (``+ score_bias``, which may be ``None``) are
    chosen and their weights are the softmax over the CHOSEN logits, times
    ``scale``: the softmax over all experts renormalised over the chosen
    (``norm_topk_prob``), in which the other logits cancel.

    Args: x ``[T, D]``; router_kernel ``[D, E]``; score_bias ``[E]``.
    Returns: expert ids ``[T, top_k]`` int32, weights ``[T, top_k]`` fp32.
    """
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring must be 'sigmoid' or 'softmax': {scoring!r}")
    scores = jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(scores)
    steered = scores if score_bias is None else scores + lax.stop_gradient(
        score_bias.astype(jnp.float32)
    )
    _, chosen = lax.top_k(steered, top_k)
    # s[t, chosen[t, j]] as a masked sum: vectorised both ways, where a
    # take_along_axis is T k scalar gathers and as many scatter-adds back
    picked = jnp.einsum(
        "tke,te->tk",
        jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32), scores,
        precision=lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        weights = jax.nn.softmax(picked, axis=-1) * scale
    else:
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weights


def _for_each_expert(x, held):
    """``[T, D]`` as ``[held, T, D]``: the batch operand of a dot over the
    experts. The compiler folds the broadcast into the dot."""
    return jnp.broadcast_to(x[None], (held, *x.shape))


@jax.custom_vjp
def _gate_up(x, gate, up):
    """``x W_gate[e]`` and ``x W_up[e]`` for every held expert: ``[T, D]``
    and two ``[held, D, F]`` (all in one dtype) to two ``[held, T, F]``.
    The expert axis is a batch dimension of both dots, so each reads its
    stack as it is stored."""
    rows = _for_each_expert(x, gate.shape[0])
    return (jnp.einsum("etd,edf->etf", rows, gate),
            jnp.einsum("etd,edf->etf", rows, up))


def _gate_up_fwd(x, gate, up):
    return _gate_up(x, gate, up), (x, gate, up)


def _gate_up_bwd(residuals, cotangents):
    """dx: one contraction over ``(held, F)`` a dot; dW: one dot batched
    over the experts for both stacks (see the section comment above)."""
    x, gate, up = residuals
    g_gate, g_up = cotangents
    dx = jnp.einsum("etf,edf->td", g_gate, gate) + jnp.einsum(
        "etf,edf->td", g_up, up
    )
    dw = jnp.einsum(
        "etd,etf->edf", _for_each_expert(x, gate.shape[0]),
        jnp.concatenate([g_gate, g_up], axis=-1),
    )
    return dx, dw[..., :gate.shape[-1]], dw[..., gate.shape[-1]:]


_gate_up.defvjp(_gate_up_fwd, _gate_up_bwd)


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _held_experts(x, share, gate, up, down, activation="silu"):
    """``sum_e share[t, e] E_e(x[t])``. Nothing of it is kept for the
    backward but its arguments: the two ``[held, T, F]`` matmul outputs
    are computed again, which costs two matmuls and saves a layer of
    them."""
    as_x = lambda w: w.astype(x.dtype)  # noqa: E731
    gated, raised = _gate_up(x, as_x(gate), as_x(up))
    return jnp.einsum(
        "etf,efd->td",
        _ACTIVATIONS[activation](gated) * raised * as_x(share).T[..., None],
        as_x(down),
    )


def local_experts(x, chosen, weights, gate, up, down, *, first_expert: int,
                  n_experts: int, activation: str = "silu"):
    """The part of a top-k expert layer that the experts held here give:
    ``y[t] = sum_j weights[t, j] E_{chosen[t, j]}(x[t])`` over the choices
    whose expert is one of ``first_expert .. first_expert + held - 1``,
    ``E(x) = W_d (act(W_g x) * W_u x)``, ``act`` the static ``activation``:
    ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU). What the other experts would
    add is left out. No token is dropped, and the work is the same whatever
    was chosen (see the section comment above).

    Args:
      x: ``[T, D]`` tokens. chosen / weights: ``[T, k]`` from
      :func:`topk_route`. gate, up: ``[held, D, F]``; down: ``[held, F, D]``
      (any float dtype; computed in ``x``'s).
      n_experts: the router's width; read by the counters only.
    Returns: ``[T, D]`` in ``x``'s dtype.

    Build-time counters, per call traced: ``moe.rows_buffered`` (the rows
    the matmuls compute, ``held x T``) and ``moe.rows_expected`` (the rows
    the router is expected to fill, ``T k held / experts``).
    """
    if activation not in _ACTIVATIONS:
        raise ValueError(
            f"activation must be one of {sorted(_ACTIVATIONS)}: {activation!r}"
        )
    n_tokens, top_k = chosen.shape
    n_held = gate.shape[0]
    reg = _registry.always()
    reg.counter("moe.rows_buffered").inc(n_held * n_tokens)
    reg.counter("moe.rows_expected").inc(
        round(n_tokens * top_k * n_held / n_experts)
    )
    with jax.named_scope("moe_experts"):
        # [T, held]: the token's weight for each held expert, zero where it
        # chose another (an index outside 0 .. held - 1 is no column)
        share = jnp.sum(
            jax.nn.one_hot(chosen - first_expert, n_held, dtype=jnp.float32)
            * weights.astype(jnp.float32)[..., None], axis=1,
        )
        return _held_experts(x, share, gate, up, down, activation)
