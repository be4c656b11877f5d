"""Driver-contract tests: entry() compiles; dryrun_multichip runs on the
virtual CPU mesh (the driver's own validation mode)."""

import sys

import jax
import pytest

pytestmark = pytest.mark.slow

sys.path.insert(0, ".")


def test_dryrun_multichip_8():
    import __graft_entry__ as ge

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    ge.dryrun_multichip(8)


def test_entry_compiles():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (8, 1000)
