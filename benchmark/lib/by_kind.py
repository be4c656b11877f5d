"""A traced run's flash kernels by the kind of layer that called them, for
the readers of a model that mixes window and full attention layers.

The program names a windowed call's three kernels ``hvd_flash_fwd_window``
/ ``_bwd_dkv_window`` / ``_bwd_dq_window`` and every other call's without
the suffix (``ops/pallas_kernels._kernel_name``), so both kinds answer to
the prefixes ``lib/by_name.kernel_ms`` asks by and are told apart here by
the end of the name. Milliseconds per step on the worst device; None where
nothing matched (no trace, or a program that names no such kernel)."""

from __future__ import annotations

from .by_name import _worst_ms_per_step
from .scopes import kernel_of

FLASH_PREFIX = "hvd_flash_"
WINDOW_SUFFIX = "_window"


def flash_ms(run, *, windowed: bool) -> float | None:
    labels = run["built"]["labels"]
    kernels = frozenset(run["built"]["pallas_call_names"])

    def wanted(name):
        if name not in kernels:
            return False
        kernel = kernel_of(labels.get(name, ""), name)
        return kernel.startswith(FLASH_PREFIX) and (
            kernel.endswith(WINDOW_SUFFIX) == windowed
        )

    return _worst_ms_per_step(run, wanted)
