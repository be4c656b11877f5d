"""Median milliseconds of one ``jax.device_put`` inside
``prefetch_to_device``, timed by the program (``input.put_ms``, the
``hvd.input.put`` span; last 512 calls). ``input_wait_ms_p50`` less this
is the source iterator and the generator around it."""

from benchmark.lib.program import snapshot


def read(run):
    return snapshot()["histograms"].get("input.put_ms", {}).get("p50")
