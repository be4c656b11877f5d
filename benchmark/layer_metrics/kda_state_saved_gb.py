"""Gigabytes of chunk-entry states the Kimi Delta Attention forward
kernels of one step leave in HBM for their backward, as the program
counted when it built its calls: ``kda.state_bytes_saved / kda.calls``
(bytes a kernel built; every build of the process books the same counts,
so the ratio is a build's) times the ``hvd_kda_*`` Mosaic calls of the
compiled step. 0 for a family that recomputes its states. Nothing to read
in a program that does not count or whose step holds no such kernel."""

from benchmark.lib.program import snapshot
from benchmark.lib.scopes import kernel_of


def read(run):
    counters = snapshot()["counters"]
    saved = counters.get("kda.state_bytes_saved")
    calls = counters.get("kda.calls")
    labels = run["built"]["labels"]
    in_step = sum(
        kernel_of(labels.get(name, ""), name).startswith("hvd_kda_")
        for name in run["built"]["pallas_call_names"]
    )
    if saved is None or not calls or not in_step:
        return None
    return saved / calls * in_step / 1e9
