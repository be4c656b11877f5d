"""Milliseconds per step in the operations under the program's
``jax.named_scope("hvd_reduce")`` or ``("hvd_loss_avg")`` that
``xla_ops_ms`` holds, i.e. that ``lib/trace.is_collective`` does not
match: since PR 29 the starts and dones of the asynchronous pairs
(``async-collective-start.N`` / ``-done.N``), which carry the scope in
their ``op_name``. Device trace, worst device. It is TIME ON THE OP
STREAM, which for a ``-done`` is waiting: not the union of the
collectives' intervals and not its exposed part (``collective_ms``,
``collective_exposed_ms``, which miss the pairs until that reader is
repaired, and will hold them, and this will fall to nothing, once it
is). Listed only for cells on more than one chip."""

from benchmark.lib.parts import REDUCE_SCOPES, under, xla_ops_ms_where


def read(run):
    return xla_ops_ms_where(run, lambda label: under(label, REDUCE_SCOPES))
