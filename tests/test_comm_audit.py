"""``tools/comm_audit.py``: the HLO collective scanner and the analytic
ring model (pure functions; no step is built here)."""

import importlib.util
import os

import pytest


@pytest.fixture(scope="module")
def ca():
    spec = importlib.util.spec_from_file_location(
        "comm_audit",
        os.path.join(os.path.dirname(__file__), "..", "tools", "comm_audit.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_comm_audit_hlo_scanner(ca):
    """The HLO collective scanner finds variadic all-reduces and sums
    operand bytes."""
    hlo = """
      %ar0 = (f32[100,4]{1,0}, bf16[8]{0}) all-reduce(%a, %b), replica_groups={}
      %ag = f32[16]{0} all-gather(%c)
      %noise = f32[2]{0} add(%d, %e)
      %ar1 = f32[10]{0} all-reduce-start(%f)
    """
    n, total, ops = ca._hlo_collectives(hlo)
    assert n == 3
    # 100*4*4 + 8*2 = 1616; 16*4 = 64; 10*4 = 40
    assert total == 1616 + 64 + 40
    assert {o["kind"] for o in ops} == {
        "all-reduce", "all-gather", "all-reduce-start"
    }

    # Regression: TPU layouts carry tiling parens — `{1,0:T(8,128)}` — that
    # broke the old `\\([^)]*\\)` tuple match (13 ARs scanned as 4 on the
    # real BERT topology audit). Variadic tuple with tiled layouts:
    tpu_hlo = (
        "  %all-reduce.2 = (f32[768,3072]{1,0:T(8,128)}, "
        "f32[768,12,64]{0,2,1:T(8,128)S(1)}) all-reduce(%p0, %p1), "
        "channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}\n"
        "  ROOT %ar = f32[30522,768]{1,0:T(8,128)} all-reduce(%p2)\n"
    )
    n2, total2, ops2 = ca._hlo_collectives(tpu_hlo)
    assert n2 == 2
    assert total2 == (768 * 3072 + 768 * 12 * 64) * 4 + 30522 * 768 * 4


def test_comm_audit_scaling_model_math(ca):
    """Ring-allreduce model: 2(n-1)/n bytes over stated link bw; the
    conservative column never exceeds the overlap-credited one."""
    row = {
        "model": "bert_base_mlm_32x512",
        "gradient_bytes_per_step": 500_000_000,
    }
    out = ca.model_scaling(row, chip="v4")
    assert [r["n_chips"] for r in out["rows"]] == [8, 16, 32]
    for r in out["rows"]:
        expect_comm = (
            2 * (r["n_chips"] - 1) / r["n_chips"] * 500e6 / (100 * 1e9) * 1e3
        )
        assert abs(r["comm_ms"] - expect_comm) < 0.01
        assert 0 < r["efficiency_no_overlap"] <= r["efficiency_with_overlap"] <= 1
    # Efficiency degrades (weakly) with world size in the no-overlap model.
    effs = [r["efficiency_no_overlap"] for r in out["rows"]]
    assert effs == sorted(effs, reverse=True)


def test_ring_allreduce_ms_known_chip(ca):
    # 1 GB over 8 chips at 90 GB/s ring: 2*(7/8) GB / 90 GB/s ≈ 19.4 ms.
    ms = ca.ring_allreduce_ms(1 << 30, 8, "v5e")
    assert ms == pytest.approx(2 * 7 / 8 * (1 << 30) / 90e9 * 1e3)
    assert ca.ring_allreduce_ms(1 << 30, 1, "v5e") == 0.0
    # No bandwidth on record: no number, never a guess.
    assert ca.ring_allreduce_ms(1 << 30, 8, "cpu") is None
