"""The model's parts on the device: one vocabulary of ``jax.named_scope``
names that every model of the program opens (``docs/api.md``), and the
predicates by which the readers under ``layer_metrics/`` cut ``xla_ops_ms``
so that every operation lands in exactly one of them.

``xla_ops_ms`` is every traced operation that is neither a Mosaic kernel
nor a collective by ``lib/trace.is_collective``. Of those, by the
``op_name`` the compiled step kept for the instruction (``labels``; a fused
instruction carries ONE, that of the operation the compiler built the
fusion around, ``lib/scopes.py``):

* no label at all: ``unlabelled_ms`` (the compiler's own ``copy-done``s);
* under ``attn_layout``, or labelled after a flash kernel without being
  one (the copies XLA sets at a kernel's door inherit the kernel's
  ``op_name``): ``flash_relayout_ms``;
* under one of :data:`PARTS` or :data:`EXPERT_SCOPES`: that scope's metric
  (``lib/by_name.scope_ms``; the program's tests hold every operation of a
  model's ``apply`` to exactly one of them, and the kernels to none);
* under ``hvd_reduce`` / ``hvd_loss_avg`` (``reduce_ms``) or ``hvd_update``
  (``update_ms``) where the trace holds more than one device: there the
  exchange stands between a gradient and its update, so both are
  instructions of their own. On one device XLA fuses each weight's update
  into the fusion that computes its gradient (PERF.md section 5), neither
  metric is reported, and the few operations that still carry those labels
  are counted with the rest;
* the rest: ``grad_unnamed_ms``.

``mtp_ms`` stays the overlapping view it was: the multi-token module lies
over a share of each part.
"""

from __future__ import annotations

from .by_name import _worst_ms_per_step
from .scopes import kernel_of
from .trace import is_collective

# what a model's ``apply`` is made of; ``attn_layout`` has no metric of its
# own name: ``flash_relayout_ms`` reads it with the copies at the kernels'
# doors
PARTS = ("embed", "norm", "attn_proj", "attn_xla", "attn_layout", "mlp",
         "head")
# the expert model's scopes of PR 36, which keep their metrics
EXPERT_SCOPES = ("mla_proj", "moe_route", "moe_experts")
MODEL_SCOPES = PARTS + EXPERT_SCOPES
REDUCE_SCOPES = ("hvd_reduce", "hvd_loss_avg")
FLASH_KERNEL_PREFIX = "hvd_flash"


def under(label: str, scopes) -> bool:
    """``label`` holds one of ``scopes`` as a whole part of its path
    (``lib/by_name.scope_ms``'s rule)."""
    parts = label.split("/")
    return any(scope in parts for scope in scopes)


def flash_kernel_scopes(built: dict) -> frozenset:
    """The names the compiled step's flash kernels were given
    (``pallas_call(name=)``), read off the Mosaic calls' own labels."""
    labels = built["labels"]
    return frozenset(
        kernel for kernel in (
            kernel_of(labels.get(name, ""), "")
            for name in built["pallas_call_names"]
        ) if kernel.startswith(FLASH_KERNEL_PREFIX)
    )


def is_relayout(label: str, flash_scopes) -> bool:
    """A labelled operation, not itself a kernel, that is the program's or
    the compiler's relayout around the flash kernels."""
    return under(label, ("attn_layout",)) or (
        kernel_of(label, "") in flash_scopes
    )


def xla_ops_ms_where(run, wanted) -> float | None:
    """Milliseconds per step on the worst device of the operations that
    ``xla_ops_ms`` counts (neither kernel nor collective) and
    ``wanted(label)`` accepts; None without a trace or a match."""
    if run["trace"] is None:
        return None
    built = run["built"]
    labels = built["labels"]
    kernels = frozenset(built["pallas_call_names"])
    collectives = frozenset(built["collective_names"])
    return _worst_ms_per_step(
        run, lambda name: name not in kernels
        and not is_collective(name, collectives)
        and wanted(labels.get(name, "")),
    )


def is_unnamed(run):
    """The predicate of ``grad_unnamed_ms`` over a label: labelled, and
    counted by no other reader of this module's cut."""
    flash_scopes = flash_kernel_scopes(run["built"])
    own = MODEL_SCOPES
    # more than one device: the exchange and the update behind it are
    # instructions of their own, with metrics of their own
    if run["trace"] is not None and len(run["trace"].devices) > 1:
        own += REDUCE_SCOPES + ("hvd_update",)
    return lambda label: bool(label) and not under(label, own) and (
        not is_relayout(label, flash_scopes)
    )
