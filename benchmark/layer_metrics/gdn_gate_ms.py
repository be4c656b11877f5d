"""Milliseconds per step in the operations under the program's
``jax.named_scope("gdn_gate")``: the Gated DeltaNet layers' softplus and
decay scale, beta's sigmoid and factor, and ``norm * SiLU(z)`` (on the
kernel path the norm's scale and the gate, the statistic being the
kernels'), forward, backward and what is run again. Device trace, worst
device (``lib/by_name.py``). Nothing to read in a program without the
scope."""

from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, "gdn_gate")
