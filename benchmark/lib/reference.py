"""The plain reference and the comparison that decides ``correct``.

One device, ``jax.jit(jax.value_and_grad(loss))`` and a bare optax
optimizer: no mesh, no framework, no kernel. The loss is the family's plain
``jax.numpy`` loss (``lib/plain_transformer.py``: float32, matmuls at
highest precision). It takes the same parameters and the same first global
batches as the system and accumulates over micro-batches.

It has the device to itself: the harness runs it once the window has closed
and the system's state is released (``lib/harness.reference_phase``), and
``add`` and ``apply`` donate what they consume and can give back. What it holds, in bytes a
float32 parameter: parameters 4, the optimizer's state 8 (AdamW's two
moments), the accumulator 4 and a micro-batch's gradients 4, so 20 and one
micro-batch's activations during a gradient and 16 at the update. Where
that does not fit the bytes the device reports, the optimizer's state waits
in host memory while gradients accumulate and comes back for the update: 12
during a gradient (:func:`plan_phase`; the bytes decide, nothing else).

``correct`` compares the loss at each of the first steps. What that cannot
see: AdamW divides a gradient by its own running scale, so a gradient that
is wrong by a constant factor (a missing 1/N in the reduction) gives the
same update and passes (PERF.md section 7).
"""

from __future__ import annotations

import functools
import math

import jax
import numpy as np
import optax

from .compile_info import planned_bytes

# The loosest relative tolerance on the loss at a compared step that any
# cell may ask for; a traffic file sets its own under ``reference.
# loss_rel_tol``, at most ten times the agreement measured on the chip for
# its cell. The system computes in bf16 (fp32 parameters, softmax and loss)
# and the reference in float32, so they differ by bf16 rounding carried
# through 12 layers, averaged over the positions the loss is a mean of.
# Measured, worst step of each run (my chip runs, PR 23; PERF.md section 6):
# 5.0e-6..1.6e-5 over 7 runs of gpt2-small.b16-s1024 (16,384 positions) and
# 2.8e-6..7.8e-6 over 4 of .dp4: their file says 1.5e-4. 1.1e-5..3.6e-5 and
# once 1.2e-4 over 7 runs of bert-base.mlm-b32-s512: its file says 1e-3.
# bert-base.cls-b96-s128-pad averages over 96 two-way labels only. At a
# constant rate from random weights AdamW's first update overshoots (loss
# 0.7 -> 2.9) and the next loss carries the whole first gradient's rounding:
# 7.7e-4..4.0e-3 at step 2 in six seeds and over 1e-2 in one of the driver's
# (at 3e-4, 4.0e-2 at step 3). Its file warms the rate up from 0, as BERT's
# fine-tuning script does, and takes this cap; the agreement over many seeds
# (benchmark/agreement.py) is in PERF.md section 6. A missing mask, a
# dropped shard or a wrong kernel moves a loss by percents.
LOSS_REL_TOL = 1e-2


def tolerance(asked) -> float:
    """The cell's tolerance: what its traffic file asks for, never looser
    than :data:`LOSS_REL_TOL`."""
    if asked is None:
        return LOSS_REL_TOL
    if not 0 < asked <= LOSS_REL_TOL:
        raise ValueError(
            f"loss_rel_tol {asked} is outside (0, {LOSS_REL_TOL}]"
        )
    return float(asked)


def tree_bytes(tree) -> int:
    """Bytes of a tree of arrays or of their shapes (``jax.eval_shape``)."""
    return sum(
        math.prod(x.shape) * np.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    )


def device_bytes_limit():
    """What the default device says it can hold (``memory_stats()``'s
    ``bytes_limit``); None where the backend keeps no such count."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


# The share of the device's limit a plan may take: a plan counts one
# program's buffers and the arrays beside it, not the allocator's
# fragmentation nor the batch and scalars in flight.
HEADROOM = 0.9


def plan_phase(*, param_bytes: int, state_bytes: int, grad_plan_bytes: int,
               bytes_limit) -> dict:
    """Where the optimizer's state waits while gradients accumulate.

    ``grad_plan_bytes`` is the gradient program's own plan
    (``compile_info.planned_bytes``: its arguments, the parameters among
    them, its outputs and its temporaries). Beside it
    live the accumulator (as large as the parameters) and, on the device,
    the optimizer's state. The update holds parameters, state and
    accumulator whichever way. ``moments`` is ``"device"`` where all of it
    fits :data:`HEADROOM` of ``bytes_limit`` (or nothing reports a limit),
    ``"host"`` where it fits only without the state, else ``"nowhere"``."""
    with_state = param_bytes + state_bytes + grad_plan_bytes
    without = param_bytes + grad_plan_bytes
    update = 2 * param_bytes + state_bytes
    room = None if bytes_limit is None else HEADROOM * bytes_limit
    if room is None or max(with_state, update) <= room:
        moments, peak = "device", max(with_state, update)
    elif max(without, update) <= room:
        moments, peak = "host", max(without, update)
    else:
        moments, peak = "nowhere", max(without, update)
    return {
        "moments": moments, "planned_peak_bytes": peak,
        "param_bytes": param_bytes, "optimizer_state_bytes": state_bytes,
        "gradient_plan_bytes": grad_plan_bytes, "bytes_limit": bytes_limit,
    }


@functools.partial(jax.jit, donate_argnums=0)
def add(acc, grads):
    return jax.tree.map(lambda a, g: a + g, acc, grads)


def make_apply(optimizer):
    """``apply(params, opt_state, acc, n)``: one optimizer step on the mean
    of ``n`` accumulated gradients. Parameters and optimizer state are
    donated; the accumulator has no output to alias (JAX would keep it and
    warn), so the caller releases it."""

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply(params, opt_state, acc, n):
        grads = jax.tree.map(lambda a: a / n, acc)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    return apply


def release(tree) -> None:
    """Deletes the device arrays of ``tree``: what follows has their bytes
    (a buffer a running program still reads is freed when that ends)."""
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def _to_host(tree):
    host = jax.device_get(tree)
    release(tree)
    return host


def make_reference(loss_fn, optimizer, *, micro_batch: int,
                   bytes_limit=device_bytes_limit):
    """``losses(params, batches)``: the loss before each of ``len(batches)``
    optimizer steps from ``params``. ``batches`` is a list of host batches
    (dicts of numpy arrays). ``params`` are consumed, and nothing the call
    allocated is live on the device when it returns. ``losses.phase`` is the
    last call's :func:`plan_phase`, from what ``bytes_limit()`` answers. The
    programs are built once, so one reference serves any number of seeds
    (``benchmark/agreement.py``)."""
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    apply = make_apply(optimizer)
    compiled = {}  # one gradient program per shape of micro-batch

    def grad_program(params, micro):
        shape = tuple((k, v.shape, str(v.dtype)) for k, v in micro.items())
        if shape not in compiled:
            compiled[shape] = grad_fn.lower(params, micro).compile()
        return compiled[shape]

    def losses(params, batches) -> list:
        opt_state = optimizer.init(params)
        first = {k: v[:micro_batch] for k, v in batches[0].items()}
        losses.phase = phase = plan_phase(
            param_bytes=tree_bytes(params), state_bytes=tree_bytes(opt_state),
            grad_plan_bytes=planned_bytes(
                grad_program(params, first).memory_analysis()
            )["peak_bytes"],
            bytes_limit=bytes_limit(),
        )
        if phase["moments"] == "nowhere":
            release((params, opt_state))
            raise MemoryError(
                "the reference does not fit the device even with its "
                f"optimizer state on the host: {phase}"
            )
        on_host = phase["moments"] == "host"
        if on_host:
            opt_state = _to_host(opt_state)
        out = []
        for batch in batches:
            n = len(next(iter(batch.values())))
            if n % micro_batch:
                raise ValueError(
                    f"micro-batch {micro_batch} does not divide the batch {n}"
                )
            acc, total = None, 0.0
            for at in range(0, n, micro_batch):
                micro = {k: v[at:at + micro_batch] for k, v in batch.items()}
                loss, grads = grad_program(params, micro)(params, micro)
                acc = grads if acc is None else add(acc, grads)
                # or the name keeps this micro-batch's gradients alive
                # through the next one's: a fifth copy of the parameters
                del grads
                total += float(loss)
            k = n // micro_batch
            if on_host:
                opt_state = jax.device_put(opt_state)
            params, opt_state = apply(params, opt_state, acc, np.float32(k))
            release(acc)
            if on_host:
                opt_state = _to_host(opt_state)
            out.append(total / k)
        jax.block_until_ready((params, opt_state))
        release((params, opt_state))
        return out

    losses.phase = None
    return losses


def compare(system: list, reference: list, tol: float = LOSS_REL_TOL) -> dict:
    rel = [
        abs(s - r) / abs(r) if math.isfinite(s) and r else float("inf")
        for s, r in zip(system, reference)
    ]
    return {
        "system": list(system), "reference": list(reference),
        "rel_diff": rel, "tolerance_rel": tol,
        "agree": len(system) == len(reference) and all(x <= tol for x in rel),
    }
