"""Operations and bytes of the window / full attention, grouped-head,
routed-expert family, computed from shapes. Nothing here is measured.
Conventions are those of ``lib/flops.py`` and ``lib/flops_latent_moe.py``;
what differs is written out.

Valid score entries of one head and sequence (``valid_entries``): a full
layer's mask leaves ``s (s + 1) / 2``; a window layer's, where row ``i``
sees the ``min(i + 1, W)`` columns ``0 <= i - j < W``, leaves ``W (W + 1) /
2 + (s - W) W`` for ``s >= W`` (16,384 under a window of 4,096: 58.7 M
against 134.2 M).

The flash kernels under a band with grouped heads (``window_flash_cost``):
what the three passes NEED, each layer's own mask counted: the forward
makes Q K^T and P V, ``4 d`` FLOPs an entry and query head; the backward
five matmuls, ``10 d`` (``lib/flops.py`` has the source; a kernel that
recomputes S and dP in each backward kernel spends 14 d, and the extra is
not needed). Tiles the kernels skip are not in the need, so skipping them
cannot read over 100%; tiles a kernel visits and masks are in its time and
not in the need, so masking without skipping reads low. Bytes: Q, O (forward)
and Q, O, dO, dQ (backward) at the ``H`` query heads, K, V and K, V, dK, dV
at the ``H_kv`` K/V heads, which is what grouped heads move and no more,
and the fp32 log-sum-exp a query head twice. A forward that
rematerialisation runs again is not a needed operation.

Training FLOPs per token (``train_flops_per_token``): ``6 N`` + attention,
N every parameter that multiplies every token (all matrices outside the
routed experts, the norm scales, the untied head) plus the EXPECTED held
share of the routed ones, ``top_k n_held / n_experts`` experts a token and
layer (8 of 64 held, top-6: 0.75). The token embedding is a lookup.
Attention's score and value matmuls are ``12 c H d`` a token and layer (4 c
H d forward, times 3) with ``c`` the columns the layer's mask lets a row
reach at most: ``s`` in a full layer (not halved for the diagonal, as
``lib/flops.py`` has it) and ``min(s, W)`` in a window layer: the band is
the model's shape, the diagonal is not counted either way.

``routed_expert_cost`` is ``lib/flops_latent_moe``'s: the three matmuls of
``E(x)`` over the expected rows, whatever activation gates them.
"""

from __future__ import annotations

from .flops_latent_moe import routed_expert_cost  # noqa: F401  (re-export)


def layer_windows(config: dict) -> list:
    """One entry a layer the configuration builds: its window, or None for
    a full layer (the published layout's first ``num_hidden_layers``
    entries)."""
    layers = config["num_hidden_layers"]
    return [config["sliding_window_size"] if on else None
            for on in config["sliding_window_layout"][:layers]]


def valid_entries(seq_len: int, window=None) -> float:
    """Score entries one head's mask leaves valid in one sequence."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) / 2
    return window * (window + 1) / 2 + (seq_len - window) * window


def window_flash_cost(*, windows, batch: int, n_heads: int, n_kv_heads: int,
                      seq_len: int, head_dim: int,
                      dtype_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes one training step needs in its flash kernels,
    forward + backward, ``batch`` sequences. ``windows``: one entry a
    layer, its window or None for a full layer."""
    entries = sum(valid_entries(seq_len, w) for w in windows)
    flops = batch * n_heads * entries * (4 + 10) * head_dim
    tensor = seq_len * head_dim * dtype_bytes  # one head of Q, K, dV, ...
    lse = seq_len * 4
    per_layer = n_heads * (6 * tensor + 2 * lse) + n_kv_heads * 6 * tensor
    return {"flops": flops, "bytes": batch * len(windows) * per_layer,
            "entries": entries}


def train_flops_per_token(*, n_always_params: int, n_expert_params: int,
                          n_layers: int, top_k: int, n_held: int,
                          n_experts: int, windows, seq_len: int,
                          n_heads: int, head_dim: int) -> float:
    """``n_expert_params``: ONE routed expert's parameters; ``windows``: as
    :func:`window_flash_cost`."""
    expected_experts = top_k * n_held / n_experts
    n = n_always_params + n_layers * expected_experts * n_expert_params
    columns = sum(seq_len if w is None else min(seq_len, w) for w in windows)
    return 6.0 * n + 12.0 * columns * n_heads * head_dim
