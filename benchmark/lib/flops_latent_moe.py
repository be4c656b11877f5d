"""Operations and bytes of the latent-attention / routed-expert family,
computed from shapes. Nothing here is measured. Conventions are those of
``lib/flops.py``; what differs is written out.

Training FLOPs per token (``train_flops_per_token``): 6 N + attention,
where N counts every parameter that multiplies every token (all matrices
outside the routed experts, the norm scales, the untied head once per use:
the multi-token module uses it a second time), plus the EXPECTED held share
of the routed ones: a token chooses ``top_k`` of ``n_experts`` experts and
``n_held`` are here, so ``top_k n_held / n_experts`` experts a token and
expert layer (JoyAI-LLM-Flash on a sixteenth: 8 x 16 / 256 = 0.5). The
token embedding is a lookup. Attention's score and value matmuls are
``6 s H (qk + v)`` a token and attention block (2 s H qk forward for the
scores, 2 s H v for the values, times 3), not halved for the mask.

The flash kernels at two head widths (``latent_flash_cost``): the forward
makes Q K^T (``qk`` wide) and P V (``v`` wide), ``2 e (qk + v)`` FLOPs a
head and sequence with ``e = s (s + 1) / 2`` score entries under the causal
mask; the backward needs five, S again, dQ = dS K and dK = dS^T Q at ``qk``
and dP = dO V^T, dV = P^T dO at ``v``: ``2 e (3 qk + 2 v)``. At 192 / 128
that is ``2 e (320 + 832)``. Bytes: the forward reads Q, K (``qk``), V
(``v``) and writes O (``v``) and the fp32 log-sum-exp; the backward reads
Q, K, V, O, dO and the log-sum-exp and writes dQ, dK, dV. A forward that
rematerialisation runs again is not a needed operation.

The routed experts (``routed_expert_cost``): the three matmuls of
``E(x)`` forward and twice that backward (for the rows and for the
weights), over the EXPECTED rows ``T top_k n_held / n_experts`` of every
expert layer, whatever implements them: rows computed beyond the expected
ones and matmuls run again in the backward are not needed operations, so
they show as a lower share. Bytes in the compute dtype: each
held expert's three matrices read forward and backward and their gradient
written, the rows read and written by each matmul.
"""

from __future__ import annotations


def train_flops_per_token(*, n_always_params: int, n_expert_params: int,
                          n_expert_layers: int, top_k: int, n_held: int,
                          n_experts: int, n_attention_blocks: int,
                          seq_len: int, n_heads: int, qk_dim: int,
                          v_dim: int) -> float:
    """``n_expert_params``: ONE routed expert's parameters."""
    expected_experts = top_k * n_held / n_experts
    n = n_always_params + n_expert_layers * expected_experts * n_expert_params
    attention = 6.0 * seq_len * n_heads * (qk_dim + v_dim)
    return 6.0 * n + n_attention_blocks * attention


def latent_flash_cost(*, n_blocks: int, batch: int, n_heads: int,
                      seq_len: int, qk_dim: int, v_dim: int,
                      dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes one training step needs in its causal flash
    kernels, forward + backward, all attention blocks, ``batch`` sequences."""
    entries = seq_len * (seq_len + 1) / 2
    per_head_flops = 2.0 * entries * ((qk_dim + v_dim)
                                      + (3 * qk_dim + 2 * v_dim))
    qk = seq_len * qk_dim * dtype_bytes  # one of Q, K, dQ, dK
    v = seq_len * v_dim * dtype_bytes  # one of V, O, dO, dV
    lse = seq_len * 4
    per_head_bytes = (2 * qk + 2 * v + lse) + (4 * qk + 4 * v + lse)
    heads = n_blocks * batch * n_heads
    return {"flops": heads * per_head_flops, "bytes": heads * per_head_bytes}


def routed_expert_cost(*, n_expert_layers: int, n_tokens: int, top_k: int,
                       n_held: int, n_experts: int, d_model: int,
                       d_expert: int, dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes one training step needs in its routed experts'
    matmuls, forward + backward, over the expected rows."""
    rows = n_tokens * top_k * n_held / n_experts
    one_matmul = 2.0 * rows * d_model * d_expert
    flops = 3 * (3 * one_matmul)  # gate, up, down; forward, d rows, d weights
    weights = 3 * n_held * d_model * d_expert * dtype_bytes
    row_bytes = rows * (d_model + d_expert) * dtype_bytes
    nbytes = 3 * weights + 3 * 3 * row_bytes
    return {"flops": n_expert_layers * flops,
            "bytes": n_expert_layers * nbytes, "rows": rows}
