"""Gigabytes of chunk-entry states the Gated DeltaNet forward kernels of
one step leave in HBM for their backward, as the program counted when it
built its calls: ``gdn.state_bytes_saved / gdn.calls`` (bytes a kernel
built; every build of the process books the same counts, so the ratio is a
build's) times the ``hvd_gdn_*`` Mosaic calls of the compiled step. The
bytes are the states' own, ``[d_v, d_k]`` a head and chunk in the compute
dtype; HBM's tiles may pad the minor axis. Nothing to read in a program
that does not count or whose step holds no such kernel."""

from benchmark.lib.program import snapshot
from benchmark.lib.scopes import kernel_of


def read(run):
    counters = snapshot()["counters"]
    saved = counters.get("gdn.state_bytes_saved")
    calls = counters.get("gdn.calls")
    labels = run["built"]["labels"]
    in_step = sum(
        kernel_of(labels.get(name, ""), name).startswith("hvd_gdn_")
        for name in run["built"]["pallas_call_names"]
    )
    if saved is None or not calls or not in_step:
        return None
    return saved / calls * in_step / 1e9
