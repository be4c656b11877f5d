"""The measured loop: one dispatch per step, the clock stamped one step
late.

Each iteration takes the next batch, dispatches one step, and only then
blocks on the PREVIOUS step's loss and stamps the clock. So the device
always holds a queued step while the host works, every step's completion
gets a stamp, and the loop is the one a user runs who logs the loss a step
late. The window ends with a last block; ``lib/stats.throughput`` and
``gaps_ms`` read the gaps between the stamps. A window that a pause of the
machine left short of ``min_steps`` whole steps runs on until it has them
(at most three times ``seconds``), so the 90th percentile always has its
samples.

With ``annotate=True`` the three spans of the loop (``input_wait``,
``dispatch``, ``sync``) and a ``StepTraceAnnotation`` per iteration go into
the profiler's trace, on the device trace's clock.
"""

from __future__ import annotations

import contextlib
import time


def run_window(step, state, batches, *, seconds=None, iterations=None,
               min_steps=0, annotate=False):
    """Runs until ``seconds`` have passed (and ``min_steps`` whole steps lie
    between the stamps) or ``iterations`` steps were dispatched. Returns ``(state, record)``; the losses in the record are
    still device arrays."""
    if (seconds is None) == (iterations is None):
        raise ValueError("give seconds or iterations, not both or neither")
    if annotate:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        span = TraceAnnotation
        step_span = lambda i: StepTraceAnnotation(  # noqa: E731
            "bench_step", step_num=i
        )
    else:
        span = step_span = lambda *_: contextlib.nullcontext()  # noqa: E731
    clock = time.perf_counter
    stamps, losses = [], []
    input_wait_s, dispatch_s, sync_s = [], [], []
    prev, i = None, 0
    t_begin = clock()
    while True:
        with step_span(i):
            t0 = clock()
            with span("input_wait"):
                batch = next(batches)
            t1 = clock()
            with span("dispatch"):
                state, loss = step(state, batch)
            t2 = clock()
            if prev is not None:
                with span("sync"):
                    prev.block_until_ready()
                t3 = clock()
                stamps.append(t3)
                sync_s.append(t3 - t2)
        input_wait_s.append(t1 - t0)
        dispatch_s.append(t2 - t1)
        losses.append(loss)
        prev = loss
        i += 1
        if iterations is not None:
            if i >= iterations:
                break
        else:
            # i dispatches give i stamps, which bound i - 1 whole steps
            elapsed = clock() - t_begin
            if elapsed >= seconds and (
                i > min_steps or elapsed >= 3 * seconds
            ):
                break
    with span("sync"):
        prev.block_until_ready()
    stamps.append(clock())
    return state, {
        "t_begin": t_begin, "stamps": stamps, "losses": losses,
        "dispatched": i, "input_wait_s": input_wait_s,
        "dispatch_s": dispatch_s, "sync_s": sync_s,
    }
