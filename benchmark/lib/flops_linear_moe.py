"""Operations and bytes of the Kimi-Delta-Attention / latent-attention /
routed-expert family, computed from shapes. Nothing here is measured.
Conventions are those of ``lib/flops.py`` and ``lib/flops_latent_moe.py``;
what differs is written out.

Kimi Delta Attention (``kda_cost``): what the RECURRENCE needs, not what a
chunked form spends, so that ``kda_roofline`` reads the same whatever
implements the kernel. A position and head: the decayed ``[d_k, d_v]``
state read by ``k`` (``2 d_k d_v`` FLOPs), written by ``k (v - .)^T`` (2)
and read by ``q`` (2): ``6 d_k d_v`` forward; the backward twice that (each
of the three products hands a gradient to both its factors): ``18 d_k d_v``
a position and head in all (32 heads of 128 x 128 over 8,192 positions:
77.3 GFLOP a layer). A chunked form adds the pairwise terms inside a chunk,
the triangle's inverse and whatever it recomputes in the backward: none of
that is needed, so it shows as a lower share. Bytes, each once: ``q``,
``k``, ``dq``, ``dk`` at ``d_k`` and ``v``, ``o``, ``do``, ``dv`` at
``d_v`` in the compute dtype; ``g`` and ``dg`` (``d_k`` a head) and ``beta`` and
``dbeta`` (one a head) in float32. States a forward leaves for its backward
are not needed bytes. At the cell's shape the bytes bound it: 0.81 GB a
layer, about 1.0 ms on a v5e against 0.4 ms of FLOPs.

Training FLOPs per token (``train_flops_per_token``): ``6 N`` + the
mixers. ``N``: every parameter that multiplies every token (all matrices
outside the routed experts, the norm scales, the convolutions' taps, the
untied head) plus the EXPECTED held share of the routed ones, ``top_k
n_held / n_experts`` experts a token and expert layer (8 of 256 held,
top-8: 0.25). The token embedding is a lookup. A latent layer's score and
value matmuls are ``6 s H (qk + v)`` a token (``lib/flops_latent_moe.py``:
not halved for the mask); a KDA layer's recurrence ``18 H d_k d_v``,
whatever the length.

``routed_expert_cost`` and ``latent_flash_cost`` are
``lib/flops_latent_moe``'s.
"""

from __future__ import annotations

from .flops_latent_moe import (  # noqa: F401  (re-exports)
    latent_flash_cost, routed_expert_cost,
)


def layer_kinds(config: dict) -> list:
    """``"kda"`` or ``"latent"`` for each layer the configuration builds,
    from the published 1-indexed lists."""
    linear = config["linear_attn_config"]
    kinds = []
    for layer in range(1, config["num_hidden_layers"] + 1):
        if layer in linear["kda_layers"]:
            kinds.append("kda")
        elif layer in linear["full_attn_layers"]:
            kinds.append("latent")
        else:
            raise ValueError(f"layer {layer} is in neither published list")
    return kinds


def kda_cost(*, batch: int, seq_len: int, n_heads: int, d_k: int, d_v: int,
             layers: int, dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes one training step needs in the recurrence of
    ``layers`` Kimi-Delta-Attention layers, forward + backward."""
    positions = batch * seq_len * n_heads
    flops = 18.0 * d_k * d_v * positions
    nbytes = positions * (
        (4 * d_k + 4 * d_v) * dtype_bytes  # q, k, dq, dk; v, o, do, dv
        + 2 * d_k * 4 + 2 * 4  # g, dg; beta, dbeta
    )
    return {"flops": layers * flops, "bytes": layers * nbytes}


def train_flops_per_token(*, n_always_params: int, n_expert_params: int,
                          n_expert_layers: int, top_k: int, n_held: int,
                          n_experts: int, n_kda_layers: int,
                          n_latent_layers: int, seq_len: int, n_heads: int,
                          kda_head_dim: int, qk_dim: int,
                          v_dim: int) -> float:
    """``n_expert_params``: ONE routed expert's parameters."""
    expected_experts = top_k * n_held / n_experts
    n = n_always_params + n_expert_layers * expected_experts * n_expert_params
    latent = 6.0 * seq_len * n_heads * (qk_dim + v_dim)
    kda = 18.0 * n_heads * kda_head_dim * kda_head_dim
    return 6.0 * n + n_latent_layers * latent + n_kda_layers * kda
