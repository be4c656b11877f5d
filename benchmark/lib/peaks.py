"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

One table for every utilization and roofline number the benchmark prints.
A kind that is not here is an error, never a default: a run on an unknown
chip has no peak to be a share of.

Source for ``TPU v5 lite`` (TPU v5e): Google Cloud documentation, "TPU v5e"
system architecture page — per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float  # FLOP/s
    int8_ops: float  # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    # what the runtime lets a process hold (``memory_stats()["bytes_limit"]``
    # on the chip): what a plan has to fit, as a rehearsal sees it
    usable_hbm_bytes: int
    ici_bits_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        usable_hbm_bytes=16_909_336_064,  # my chip run, PR 26
        ici_bits_per_s=1600e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


class UnknownChip(Exception):
    pass


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownChip(
            f"no peak on record for device kind {device_kind!r}; add a row "
            "to benchmark/lib/peaks.py with its source"
        ) from None
