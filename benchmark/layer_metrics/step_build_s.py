"""Seconds the process spent building the train step, every time it did:
tracing, lowering and compiling (a persistent-cache hit included), as the
program booked them (``build.trace_s`` / ``lower_s`` / ``compile_s`` of
``hvd_train_step``). The share of ``setup_s`` that is the step's own."""

from benchmark.lib.program import step_builds


def read(run):
    seconds = [v for k, v in step_builds().items() if k.endswith("_s")]
    return sum(seconds) if seconds else None
