"""Seconds the step builder spends tracing and lowering the step
(benchmark clock around ``step.lower``). The persistent cache cannot
shorten it."""


def read(run):
    return run["built"]["lower_s"]
