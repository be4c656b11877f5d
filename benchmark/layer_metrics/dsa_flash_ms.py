"""Milliseconds per step in the flash kernels that run under a mask they
are handed (``pallas_call`` names that start with ``hvd_flash_`` and end in
``_select``: forward, dK/dV and dQ over the kept set): device trace, worst
device, with a forward that rematerialisation runs again. Nothing to read
without a trace or in a program that names no such kernel."""

from benchmark.lib.by_name import _worst_ms_per_step
from benchmark.lib.scopes import kernel_of


def read(run):
    labels = run["built"]["labels"]
    kernels = frozenset(run["built"]["pallas_call_names"])

    def wanted(name):
        if name not in kernels:
            return False
        kernel = kernel_of(labels.get(name, ""), name)
        return kernel.startswith("hvd_flash_") and kernel.endswith("_select")

    return _worst_ms_per_step(run, wanted)
