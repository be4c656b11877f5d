"""Seconds XLA spends compiling the lowered step, or reading it from the
persistent cache (benchmark clock around ``.compile()``; the ``compile``
line says which)."""


def read(run):
    return run["built"]["compile_s"]
