"""Analytic FLOP / peak-throughput model behind the step-metrics
instrumentation's ``step.mfu`` gauge: the nominal bf16 peaks per TPU
generation, the transformer 6N+attention rule of thumb, and a ResNet-50
constant. (The benchmark keeps its own, ``benchmark/lib/flops.py`` and
``peaks.py``: it may import nothing of the program it measures.)
"""

from __future__ import annotations

from typing import Optional

# Nominal bf16 peak by TPU generation (per chip). Sources: public TPU
# system documentation.
PEAK_TFLOPS_BF16 = {
    "v4": 275.0,
    "v5 lite": 197.0,  # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,  # v6e (Trillium)
    "v6e": 918.0,
}

# ResNet-50 v1.5 @ 224x224: ~4.11 GFLOP forward, x3 for fwd+bwd.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 4.11e9


def peak_tflops(device) -> float:
    """Nominal bf16 peak for a jax device. NaN only for a ``cpu`` device
    (the test mesh), so MFU math there propagates un-claimable; any other
    platform whose ``device_kind`` is not in :data:`PEAK_TFLOPS_BF16` is
    an error — a missing row must not turn into a silent ``mfu: null``."""
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in PEAK_TFLOPS_BF16.items():
        if key in kind:
            return peak
    if getattr(device, "platform", "") == "cpu":
        return float("nan")
    raise ValueError(
        f"no bf16 peak on record for device kind {kind!r} "
        f"(platform {getattr(device, 'platform', '?')!r}); add it to "
        "horovod_tpu.obs.flops.PEAK_TFLOPS_BF16 with its source"
    )


def transformer_flops_per_token(
    n_params: int, n_layers: int, seq_len: int, d_model: int
) -> float:
    """Training FLOPs per token: the 6N convention (matmul-participating
    params only — pass ``n_params`` with embedding lookup tables already
    excluded) plus the 12*L*s*d attention term."""
    return 6.0 * n_params + 12.0 * n_layers * seq_len * d_model


def mfu(
    tokens_per_sec: float, flops_per_token: float, device=None,
    peak: Optional[float] = None,
) -> Optional[float]:
    """Model FLOPs utilization; None on a ``cpu`` device (no peak to
    claim against — :func:`peak_tflops` raises for any other unknown)."""
    if peak is None:
        import jax

        peak = peak_tflops(device if device is not None else jax.devices()[0])
    if not peak or peak != peak:  # 0 or NaN
        return None
    return tokens_per_sec * flops_per_token / 1e12 / peak
