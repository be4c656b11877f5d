"""Operations and bytes of the learned-sparse-attention, grouped-head,
routed-expert family, computed from shapes. Nothing here is measured.
Conventions are those of ``lib/flops.py`` and ``lib/flops_window_moe.py``;
what differs is written out.

Pairs of one sequence: ``causal_entries`` (``s (s + 1) / 2``, what the
indexer scores) and ``kept_entries`` (row ``t`` keeps ``min(t + 1,
topk)``: ``topk (topk + 1) / 2 + (s - topk) topk``; 14,681,088 of
33,558,528 at 8,192 under 2,048, 43.75%). Ties beyond ``topk`` are not
counted.

The three kernel groups' NEEDS for one training step (each ``*_cost``
returns ``flops`` and ``bytes``); what a kernel spends beyond them is in its
time and not in its need, so its roofline reads low and none can pass 100%:

* ``select_cost``: the indexer's products, ``2 H_I d_I`` FLOPs a CAUSAL
  pair (every earlier position has to be scored before one can be dropped;
  the ReLU, the weighted sum over heads and the threshold's 32 rounds of
  compare-and-count are VPU work and not in the need); the indexer's
  queries, key and weights read once and the int8 mask written once.
* ``masked_flash_cost``: a grouped-query layer's forward and backward over
  the KEPT pairs, ``(4 + 10) d`` FLOPs a pair and query head; q, o, do, dq
  at the query heads, k, v, dk, dv at the K/V heads, the fp32 log-sum-exp a
  query head twice, and the mask read once a kernel (three times). Random
  weights scatter the kept set over every tile, so a kernel that masks
  without skipping does a causal layer's work for 43.75% of its entries and
  reads under 44% of what ``gqa_flash_roofline`` would.
* ``kl_cost``: over the kept pairs, one ``Q K^T`` a query head (``2 d``
  FLOPs a pair and head: the target's probabilities) and the indexer's
  product once forward and twice back (``3 x 2 H_I d_I`` a pair); q, k, the
  log-sum-exp, the indexer's operands and the mask read once, the three
  gradients written once.

Training FLOPs per token (``train_flops_per_token``): ``6 N`` with N as in
``lib/flops_window_moe`` (the indexer's three projections are matrices that
multiply every token), plus, a layer and token: attention's score and value
matmuls over the kept pairs, ``12 c_kept H d`` with ``c_kept =
kept_entries / s`` (1,792.1 at 8,192: where the band of the window family is
the model's shape, here the kept set is, and it is counted as it is, not
rounded up to ``topk``); the indexer's scoring of every causal pair, ``2 c_causal
H_I d_I`` (forward only: no gradient passes the selection); and the index
loss over the kept pairs, ``c_kept (2 H d + 6 H_I d_I)`` as ``kl_cost``.
"""

from __future__ import annotations

from .flops_latent_moe import routed_expert_cost  # noqa: F401  (re-export)


def causal_entries(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def kept_entries(seq_len: int, topk: int) -> int:
    """Score entries one sequence keeps: row ``t`` its ``min(t + 1,
    topk)`` (ties beyond ``topk`` not counted)."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def shapes(config: dict, traffic: dict) -> dict:
    """The arguments the three ``*_cost`` functions share, from a
    configuration and a traffic file."""
    sparse = config["sa_config"]
    return dict(
        layers=config["num_hidden_layers"], batch=traffic["per_chip_batch"],
        seq_len=traffic["seq_len"], topk=sparse["topk"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        index_heads=sparse["indexer_num_heads"],
        index_head_dim=sparse["indexer_head_dim"],
    )


def floor_seconds(run, cost_of):
    """For the ``dsa_*_roofline`` readers: the least seconds the run's chip
    could take for ``cost_of``'s need at the run's cell, or None without a
    known chip or in a configuration without ``sa_config``."""
    from .flops import roofline

    config, peak = run["cell"].config, run["peak"]
    if peak is None or "sa_config" not in config:
        return None
    cost = cost_of(**shapes(config, run["cell"].traffic))
    return roofline(
        cost["flops"], cost["bytes"], peak.bf16_flops, peak.hbm_bytes_per_s
    )["seconds"]


def share_of_floor(run, cost_of, measured_ms):
    """``100 floor / measured``, or None where either is missing."""
    if not measured_ms:
        return None
    floor = floor_seconds(run, cost_of)
    return None if floor is None else 100.0 * floor * 1e3 / measured_ms


def select_cost(*, layers, batch, seq_len, index_heads, index_head_dim,
                dtype_bytes: int = 2, **_) -> dict:
    flops = 2.0 * index_heads * index_head_dim * causal_entries(seq_len)
    nbytes = (
        seq_len * (index_heads + 1) * index_head_dim * dtype_bytes  # qI, kI
        + seq_len * index_heads * 4  # w
        + seq_len * seq_len  # the mask, int8
        + 2 * seq_len * 4  # tau, lse_I
    )
    return {"flops": layers * batch * flops, "bytes": layers * batch * nbytes}


def masked_flash_cost(*, layers, batch, seq_len, topk, n_heads, n_kv_heads,
                      head_dim, dtype_bytes: int = 2, **_) -> dict:
    kept = kept_entries(seq_len, topk)
    flops = n_heads * kept * (4.0 + 10.0) * head_dim
    tensor = seq_len * head_dim * dtype_bytes  # one head of Q, K, dV, ...
    nbytes = (n_heads * (6 * tensor + 2 * seq_len * 4)
              + n_kv_heads * 6 * tensor + 3 * seq_len * seq_len)
    return {"flops": layers * batch * flops, "bytes": layers * batch * nbytes,
            "entries": kept}


def kl_cost(*, layers, batch, seq_len, topk, n_heads, n_kv_heads, head_dim,
            index_heads, index_head_dim, dtype_bytes: int = 2, **_) -> dict:
    kept = kept_entries(seq_len, topk)
    flops = kept * (2.0 * n_heads * head_dim
                    + 6.0 * index_heads * index_head_dim)
    index_bytes = (seq_len * (index_heads + 1) * index_head_dim * dtype_bytes
                   + seq_len * index_heads * 4)
    nbytes = (
        seq_len * (n_heads + n_kv_heads) * head_dim * dtype_bytes  # q, k
        + n_heads * seq_len * 4 + seq_len * 4  # lse a head, lse_I
        + seq_len * seq_len  # the mask
        + 2 * index_bytes  # the indexer's operands read, gradients written
    )
    return {"flops": layers * batch * flops, "bytes": layers * batch * nbytes}


def train_flops_per_token(*, n_always_params: int, n_expert_params: int,
                          n_layers: int, top_k: int, n_held: int,
                          n_experts: int, seq_len: int, topk: int,
                          n_heads: int, head_dim: int, index_heads: int,
                          index_head_dim: int) -> float:
    """``n_expert_params``: ONE routed expert's parameters; ``top_k``: the
    router's; ``topk``: the indexer's."""
    expected_experts = top_k * n_held / n_experts
    n = n_always_params + n_layers * expected_experts * n_expert_params
    c_kept = kept_entries(seq_len, topk) / seq_len
    c_causal = causal_entries(seq_len) / seq_len
    index = index_heads * index_head_dim
    per_layer = (12.0 * c_kept * n_heads * head_dim + 2.0 * c_causal * index
                 + c_kept * (2.0 * n_heads * head_dim + 6.0 * index))
    return 6.0 * n + n_layers * per_layer
