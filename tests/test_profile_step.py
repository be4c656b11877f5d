"""tools/profile_step.py: the converter-absent branch must be actionable.

Satellite of the telemetry PR: without TensorFlow (whose bundled pybind
converts xplane→hlo_stats) the tool used to die with a bare
ImportError traceback; now it raises :class:`ConverterUnavailable` with
an install hint, and ``main`` exits with a clean message.
"""

import importlib.util
import os
import sys

import pytest


def _load():
    spec = importlib.util.spec_from_file_location(
        "profile_step",
        os.path.join(
            os.path.dirname(__file__), "..", "tools", "profile_step.py"
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hide_tensorflow(monkeypatch):
    """Simulate "tensorflow not installed": None in sys.modules makes an
    import raise ImportError — including every already-imported submodule
    (a dotted import short-circuits on the cached full name, so the bare
    parent entry alone is not enough once TF was imported earlier in the
    test session)."""
    monkeypatch.setitem(sys.modules, "tensorflow", None)
    for name in list(sys.modules):
        if name.startswith("tensorflow."):
            monkeypatch.setitem(sys.modules, name, None)


def test_converter_absent_is_actionable(monkeypatch):
    ps = _load()
    _hide_tensorflow(monkeypatch)
    with pytest.raises(ps.ConverterUnavailable) as ei:
        ps._load_converter()
    msg = str(ei.value)
    assert "tensorflow>=2.x" in msg
    assert "--out-dir" in msg  # tells the user how to salvage the trace


def test_converter_absent_from_xplane_entry(monkeypatch, tmp_path):
    ps = _load()
    _hide_tensorflow(monkeypatch)
    # The converter check fires before any trace-dir scanning, so the
    # error is the clear one even when a trace exists.
    (tmp_path / "t.xplane.pb").write_bytes(b"")
    with pytest.raises(ps.ConverterUnavailable):
        ps.xplane_to_hlo_stats(str(tmp_path))


def test_categorize_unchanged():
    # The category rollup (the tool's analysis half) works with no TF.
    ps = _load()
    assert ps.categorize("fused_all-reduce.1") == "allreduce"
    assert ps.categorize("convolution.3") == "conv"
    assert ps.categorize("reduce.7") == "bn_reduce"
    assert ps.categorize("weird_op") == "other"
