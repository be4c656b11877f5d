import pytest

from benchmark.lib import flops, peaks


def test_gpt2_small_is_855_mflop_per_token():
    n_total = 124_439_808
    n_matmul = n_total - 1024 * 768  # the position table is a lookup
    assert n_matmul == 123_653_376
    f = flops.transformer_train_flops_per_token(n_matmul, 12, 1024, 768)
    assert f == 6 * 123_653_376 + 12 * 12 * 1024 * 768
    assert round(f / 1e6) == 855


def test_flash_cost_non_causal_by_hand():
    # one layer, one sequence, one head, s = 4, d = 2, bf16
    c = flops.flash_attention_cost(
        n_layers=1, batch=1, n_heads=1, seq_len=4, head_dim=2, causal=False
    )
    one_matmul = 2 * 4 * 4 * 2  # 64 FLOPs
    assert c["flops"] == 7 * one_matmul
    tensor = 4 * 2 * 2  # bytes of one [s, d] bf16 tensor
    lse = 4 * 4
    assert c["bytes"] == (4 * tensor + lse) + (8 * tensor + lse)


def test_flash_cost_causal_counts_the_lower_triangle():
    full = flops.flash_attention_cost(
        n_layers=2, batch=3, n_heads=5, seq_len=8, head_dim=4, causal=False
    )
    causal = flops.flash_attention_cost(
        n_layers=2, batch=3, n_heads=5, seq_len=8, head_dim=4, causal=True
    )
    assert causal["flops"] / full["flops"] == pytest.approx((8 * 9 / 2) / 64)
    assert causal["bytes"] == full["bytes"]


def test_roofline_says_which_peak_binds():
    r = flops.roofline(2e12, 1e9, peak_flops=1e12, peak_bytes_per_s=1e9)
    assert r["bound"] == "compute" and r["seconds"] == pytest.approx(2.0)
    r = flops.roofline(1e12, 3e9, peak_flops=1e12, peak_bytes_per_s=1e9)
    assert r["bound"] == "memory" and r["seconds"] == pytest.approx(3.0)


def test_v5e_row_and_unknown_kind():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s, p.hbm_bytes,
            p.ici_bits_per_s) == (197e12, 393e12, 819e9, 16e9, 1600e9)
    for kind in ("cpu", "TPU v4", "TPU v5", ""):
        with pytest.raises(peaks.UnknownChip):
            peaks.peak_for(kind)
