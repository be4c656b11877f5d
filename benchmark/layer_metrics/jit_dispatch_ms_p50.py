"""Median milliseconds of JAX's own dispatch of the jitted step, timed by
the program around the jitted function (``step.jit_dispatch_ms``, the
``hvd.step.jit`` span; the histogram keeps the last 512 calls).
``dispatch_ms_p50`` less this is what the framework's wrappers cost."""

from benchmark.lib.program import snapshot


def read(run):
    return snapshot()["histograms"].get("step.jit_dispatch_ms", {}).get("p50")
