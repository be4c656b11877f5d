"""Gigabytes of int8 masks the select kernels of one step leave in HBM
(each kept for its layer's backward), as the program counted when it built
its calls: ``dsa.mask_bytes / dsa.calls`` (bytes a call built; every build
of the process books the same counts, so the ratio is a build's) times the
``hvd_dsa_select`` Mosaic calls of the compiled step. Nothing to read in a
program that does not count or whose step holds no such kernel."""

from benchmark.lib.program import snapshot
from benchmark.lib.scopes import kernel_of


def read(run):
    counters = snapshot()["counters"]
    nbytes = counters.get("dsa.mask_bytes")
    calls = counters.get("dsa.calls")
    labels = run["built"]["labels"]
    in_step = sum(
        kernel_of(labels.get(name, ""), name).startswith("hvd_dsa_select")
        for name in run["built"]["pallas_call_names"]
    )
    if nbytes is None or not calls or not in_step:
        return None
    return nbytes / calls * in_step / 1e9
