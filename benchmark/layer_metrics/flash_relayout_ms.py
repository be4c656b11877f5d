"""Milliseconds per step in the XLA operations that stand between the
model's projections and the flash kernels, both directions: those under
the program's ``jax.named_scope("attn_layout")`` (the models' reshapes and
transposes into and out of the kernels' layout, and the kernels' entry's
own glue: padding, the row statistics' layout, ``rowsum(g * out)``, the
slices back) and those that are not Mosaic calls but whose label ends in
a flash kernel's ``pallas_call`` name (the copies XLA sets at a kernel's
door inherit the kernel's ``op_name``). Device trace, worst device; no
kernel is counted (``flash_ms`` has those); ``lib/parts.py``. Nothing to
read where attention bypasses the kernels."""

from benchmark.lib.parts import (
    flash_kernel_scopes, is_relayout, xla_ops_ms_where,
)


def read(run):
    flash_scopes = flash_kernel_scopes(run["built"])
    return xla_ops_ms_where(
        run, lambda label: is_relayout(label, flash_scopes)
    )
