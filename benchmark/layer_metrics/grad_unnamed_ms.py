"""Milliseconds per step in the labelled operations of ``xla_ops_ms`` that
lie under no scope a model opens (``lib/parts.MODEL_SCOPES``), are no
relayout around the flash kernels and, on more than one device, are not
under ``hvd_reduce`` / ``hvd_loss_avg`` / ``hvd_update``: under
``hvd_grad`` that is the USER's loss on the logits (the families call
``optax``; the program cannot name it) and whatever a model left out; on
one device also the few operations that still carry ``hvd_update`` (XLA
fuses the rest into the gradients' fusions). Device trace, worst device;
``lib/parts.py``. With the parts, the expert model's scopes,
``flash_relayout_ms`` and ``unlabelled_ms`` it adds up to ``xla_ops_ms``."""

from benchmark.lib.parts import is_unnamed, xla_ops_ms_where


def read(run):
    return xla_ops_ms_where(run, is_unnamed(run))
