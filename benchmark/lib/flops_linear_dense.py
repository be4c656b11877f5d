"""Operations and bytes of the Gated-DeltaNet / full-attention / dense-FFN
family, computed from shapes. Nothing here is measured. Conventions are
those of ``lib/flops.py`` and ``lib/flops_linear_moe.py``; what differs is
written out.

Gated DeltaNet (``gdn_cost``): what the RECURRENCE needs, not what a
chunked form spends, so that ``gdn_roofline`` reads the same whichever form
of the kernels runs (the scalar-gate chunk with or without its door and
exit, or the channel-wise kernels on a broadcast gate). A position and
head: the decayed ``[d_k, d_v]`` state read by ``k`` (``2 d_k d_v`` FLOPs),
written by ``k (v - .)^T`` (2) and read by ``q`` (2): ``6 d_k d_v``
forward; the backward twice that: ``18 d_k d_v`` a position and head in
all (15 heads of 96 x 192 over 8,192 positions: 40.8 GFLOP a layer).
Bytes, each once: ``q``, ``k``, ``dq``, ``dk`` at ``d_k`` and ``v``, ``o``,
``do``, ``dv`` at ``d_v`` in the compute dtype; ``g``, ``dg``, ``beta``,
``dbeta`` ONE a head in float32 (where ``lib/flops_linear_moe.kda_cost``
has ``g`` and ``dg`` one a key channel). States a forward leaves for its
backward, the convolution and the norm a form does at its door and exit
are not needed bytes or operations. At the cell's shape the bytes bound
it: 0.285 GB a layer, about 0.35 ms on a v5e against 0.21 ms of FLOPs.

Training FLOPs per token (``train_flops_per_token``): ``6 N`` + the
mixers. ``N``: every parameter that multiplies every token (all matrices,
the norm scales, the taps, the untied head; nothing is routed). The token
embedding is a lookup. A full layer's score and value matmuls are ``12 s H
d`` a token (not halved for the mask); a Gated DeltaNet layer's recurrence
``18 H d_k d_v``, whatever the length.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def layer_kinds(config: dict) -> list:
    """The published ``layer_types`` entry of each layer the configuration
    builds (its first ``num_hidden_layers``)."""
    kinds = list(config["layer_types"][:config["num_hidden_layers"]])
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {LINEAR, FULL}:
        raise ValueError(f"layer_types gives {kinds}")
    return kinds


def gdn_cost(*, batch: int, seq_len: int, n_heads: int, d_k: int, d_v: int,
             layers: int, dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes one training step needs in the recurrence of
    ``layers`` Gated DeltaNet layers, forward + backward."""
    positions = batch * seq_len * n_heads
    flops = 18.0 * d_k * d_v * positions
    nbytes = positions * (
        (4 * d_k + 4 * d_v) * dtype_bytes  # q, k, dq, dk; v, o, do, dv
        + 4 * 4  # g, dg, beta, dbeta
    )
    return {"flops": layers * flops, "bytes": layers * nbytes}


def train_flops_per_token(*, n_matmul_params: int, n_linear_layers: int,
                          n_full_layers: int, seq_len: int, n_heads: int,
                          head_dim: int, d_k: int, d_v: int) -> float:
    full = 12.0 * seq_len * n_heads * head_dim
    linear = 18.0 * n_heads * d_k * d_v
    return (6.0 * n_matmul_params + n_full_layers * full
            + n_linear_layers * linear)
