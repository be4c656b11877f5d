"""Share of their roofline the flash kernels reach: the least time the
chip could take for the operations and bytes the three passes need
(``lib/flops.flash_attention_cost``, mask counted, against the peaks in
``lib/peaks.py``) over ``flash_ms``. Nothing to read where the cell's
attention bypasses the kernels. The ``trace`` line's ``flash_bound`` says
which peak applies."""

from benchmark.lib.flops import flash_attention_cost, roofline


def floor_seconds(run):
    shapes = run["family"].flash
    if shapes is None or run["peak"] is None:
        return None
    cost = flash_attention_cost(
        batch=run["cell"].traffic["per_chip_batch"], **shapes
    )
    peak = run["peak"]
    return roofline(
        cost["flops"], cost["bytes"], peak.bf16_flops, peak.hbm_bytes_per_s
    )


def read(run):
    t = run["trace"]
    floor = floor_seconds(run)
    if t is None or floor is None:
        return None
    measured_ms = t.per_step_ms("kernels_s")
    if measured_ms <= 0:
        return None
    return 100.0 * floor["seconds"] * 1e3 / measured_ms
