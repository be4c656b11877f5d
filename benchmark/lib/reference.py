"""The plain reference and the comparison that decides ``correct``.

One device, ``jax.jit(jax.value_and_grad(loss))`` and a bare optax
optimizer: no mesh, no framework, no kernel. The loss is the family's plain
``jax.numpy`` loss (``lib/plain_transformer.py``: float32, matmuls at
highest precision). It takes the same parameters and the same first global
batches as the system and accumulates over micro-batches so that it fits
beside the system's state.

``correct`` compares the loss at each of the first steps. What that cannot
see: AdamW divides a gradient by its own running scale, so a gradient that
is wrong by a constant factor (a missing 1/N in the reduction) gives the
same update and passes. A gradient-norm counter from the program would
close that; it is the ``tracing`` issue's.
"""

from __future__ import annotations

import math

import jax
import numpy as np
import optax

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


def make_reference(loss_fn, optimizer, *, micro_batch: int):
    """``losses(params, batches)``: the loss before each of ``len(batches)``
    optimizer steps from ``params``. ``batches`` are host batches (dicts of
    numpy arrays). The programs are built once, so one reference serves any
    number of seeds (``benchmark/agreement.py``)."""
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    @jax.jit
    def add(acc, grads):
        return jax.tree.map(lambda a, g: a + g, acc, grads)

    @jax.jit
    def apply(params, opt_state, acc, n):
        grads = jax.tree.map(lambda a: a / n, acc)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def losses(params, batches) -> list:
        opt_state = optimizer.init(params)
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
                loss, grads = grad_fn(params, micro)
                acc = grads if acc is None else add(acc, grads)
                total += float(loss)
            k = n // micro_batch
            params, opt_state = apply(params, opt_state, acc, np.float32(k))
            out.append(total / k)
        return out

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
