"""Operations and bytes computed from shapes. Nothing here is measured.

Training FLOPs per token (``transformer_train_flops_per_token``) follow the
6N + 12*L*s*d convention (Kaplan et al. 2020, arXiv:2001.08361, section 2.1;
PaLM, arXiv:2204.02311, appendix B):

* N counts every parameter that takes part in a matrix multiplication with
  every token: all kernels, biases and LayerNorm scales, and a TIED
  embedding (it is the output head's matrix). It leaves out pure lookup
  tables: the position table, the token-type table, and an untied token
  embedding. Each parameter costs 2 FLOPs forward and 4 backward.
* 12*L*s*d is attention's score and value matmuls (4*s*d forward per token
  and layer, times 3 for forward + backward). It is NOT halved for a causal
  mask and NOT reduced for padding: a token is a position of the batch, and
  MFU is against the dense work the model's shapes stand for.
* Recomputed operations (remat, the flash backward's second pass over the
  scores) do not count.

GPT-2-small (N = 123,653,376; L 12, s 1024, d 768): 854.8 MFLOP/token.

The flash-attention cost (``flash_attention_cost``) is what the three passes
of a flash kernel NEED, mask counted: the forward pass makes 2 matmuls of
2*s*s*d FLOPs per head and sequence (Q K^T, P V), the backward 5 (S again,
dP, dV, dK, dQ; FlashAttention-2, arXiv:2307.08691, section 3.2 — a kernel
that recomputes S and dP once per backward kernel, as the repo's two
backward kernels do, spends 7, and the extra 2 are not needed operations).
Under a causal mask only s*(s+1)/2 of the s*s score entries exist. Bytes:
the forward reads Q, K, V and writes O and the fp32 log-sum-exp; the
backward reads Q, K, V, O, dO and the log-sum-exp and writes dQ, dK, dV.
"""

from __future__ import annotations


def transformer_train_flops_per_token(
    n_matmul_params: int, n_layers: int, seq_len: int, d_model: int
) -> float:
    return 6.0 * n_matmul_params + 12.0 * n_layers * seq_len * d_model


def flash_attention_cost(
    *, n_layers: int, batch: int, n_heads: int, seq_len: int, head_dim: int,
    causal: bool, dtype_bytes: int = 2,
) -> dict:
    """FLOPs and HBM bytes one training step needs in its flash kernels
    (forward + backward, all layers) for ``batch`` sequences on one chip."""
    entries = seq_len * (seq_len + 1) / 2 if causal else seq_len * seq_len
    matmul = 2.0 * entries * head_dim  # one s x s x d matmul, masked
    per_head_flops = (2 + 5) * matmul
    tensor = seq_len * head_dim * dtype_bytes  # one of Q, K, V, O, dO, ...
    lse = seq_len * 4
    per_head_bytes = (4 * tensor + lse) + (8 * tensor + lse)
    heads = n_layers * batch * n_heads
    return {
        "flops": heads * per_head_flops,
        "bytes": heads * per_head_bytes,
    }


def roofline(flops: float, nbytes: float, peak_flops: float,
             peak_bytes_per_s: float) -> dict:
    """The least time the chip could take, and which peak sets it."""
    t_compute = flops / peak_flops
    t_memory = nbytes / peak_bytes_per_s
    return {
        "seconds": max(t_compute, t_memory),
        "bound": "compute" if t_compute >= t_memory else "memory",
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
    }
