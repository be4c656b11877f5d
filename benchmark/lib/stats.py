"""Percentiles and the stamps-to-throughput arithmetic."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default). Raises on an empty list."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartiles(values: Sequence[float]) -> dict:
    return {
        "n": len(values),
        "p25": percentile(values, 25),
        "p50": percentile(values, 50),
        "p75": percentile(values, 75),
        "p90": percentile(values, 90),
        "min": min(values),
        "max": max(values),
    }


def gaps_ms(stamps: Sequence[float]) -> list:
    """Milliseconds between successive step-completion stamps (seconds)."""
    return [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]


def throughput(stamps: Sequence[float], tokens_per_step: int,
               chips: int) -> dict:
    """Tokens per second per chip at the MEDIAN gap between successive
    step-completion stamps. ``n`` stamps bound ``n - 1`` whole steps, so the
    un-hidden first dispatch before the first stamp is outside the count.

    The median and not steps over time: on a one-chip machine, whose host
    cores are shared, one ``sync`` in a run of three was seen to return 3
    to 14 s late with the device long done (PERF.md section 6). Over the
    whole window that one pause of the machine cost 17% to 70% of the
    reading, no bound could hold, and no change to the program could move
    it. The median gap does not see it; a slowdown of more than a tenth of
    the steps moves ``step_ms_p90``. What falls between (rare long stalls
    of the program's own) shows in the whole-window figure, which is kept
    beside it for the earlier ``window`` line."""
    if len(stamps) < 2:
        raise ValueError("throughput needs at least two stamps")
    steps = len(stamps) - 1
    seconds = stamps[-1] - stamps[0]
    median_s = percentile(gaps_ms(stamps), 50) / 1e3
    return {
        "steps": steps,
        "seconds": seconds,
        "tokens_per_s_per_chip": tokens_per_step / median_s / chips,
        "whole_window_tokens_per_s_per_chip":
            steps * tokens_per_step / seconds / chips,
    }
