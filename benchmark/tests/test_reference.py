"""The plain reference is the same mathematics as the program's model, for
every family: on the CPU at the files' ``tiny`` sizes the program (bf16)
and the reference (float32) agree to bf16 rounding."""

import os

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark.lib import data, reference, resolve

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")
CELLS = ["gpt2-small.b16-s1024", "bert-base.mlm-b32-s512",
         "bert-base.cls-b96-s128-pad"]


def _tiny(workload):
    manifest = resolve.load_manifest(ROOT)
    w = resolve.find_workload(manifest, workload)
    config = resolve.load_config(ROOT, manifest, w["config"])
    traffic = resolve.load_traffic(BENCH, w["traffic"])
    config = {**config, **config["tiny"]}
    traffic = {**traffic, **traffic["tiny"]}
    family = resolve.load_family(BENCH, traffic["family"]).build(
        config, traffic
    )
    pool = data.make_pool(
        traffic["data"], vocab_size=family.vocab_size,
        global_batch=traffic["per_chip_batch"], seq_len=traffic["seq_len"],
        n_batches=3, seed=0,
    )
    return family, pool


@pytest.mark.parametrize("workload", CELLS)
def test_plain_loss_and_gradient_match_the_program(workload):
    family, pool = _tiny(workload)
    params = family.init_params(jax.random.PRNGKey(0))
    ref_loss, ref_grad = jax.value_and_grad(family.reference_loss)(
        params, pool[0]
    )
    sys_loss, sys_grad = jax.value_and_grad(family.loss_fn)(params, pool[0])
    # the system computes in bf16: the loss agrees to bf16 rounding, the
    # gradients point the same way
    assert float(sys_loss) == pytest.approx(float(ref_loss), rel=5e-3)
    flat = lambda t: jnp.concatenate(  # noqa: E731
        [x.ravel().astype(jnp.float32) for x in jax.tree.leaves(t)]
    )
    a, b = flat(ref_grad), flat(sys_grad)
    cosine = float(a @ b / (jnp.linalg.norm(a) * jnp.linalg.norm(b)))
    assert cosine > 0.99
    assert jax.tree.structure(ref_grad) == jax.tree.structure(sys_grad)


def test_micro_batches_give_the_whole_batch_gradient():
    family, pool = _tiny("bert-base.cls-b96-s128-pad")
    opt = optax.adamw(3e-4)

    def losses(micro_batch):  # the reference consumes its parameters
        return reference.make_reference(
            family.reference_loss, opt, micro_batch=micro_batch
        )(family.init_params(jax.random.PRNGKey(1)), pool)

    assert losses(4) == pytest.approx(losses(2), rel=1e-5)
    with pytest.raises(ValueError):
        losses(3)


def test_compare_decides_on_every_step():
    ok = reference.compare([1.0, 2.0, 3.0], [1.001, 2.0, 2.999], tol=1e-2)
    assert ok["agree"] and max(ok["rel_diff"]) < 1.1e-3
    assert not reference.compare([1.0, 2.0, 3.2], [1.0, 2.0, 3.0],
                                 tol=1e-2)["agree"]
    assert not reference.compare([1.0, float("nan")], [1.0, 2.0])["agree"]
    assert not reference.compare([1.0], [1.0, 2.0])["agree"]


def test_a_cell_may_tighten_the_tolerance_and_never_loosen_it():
    assert reference.LOSS_REL_TOL == 1e-2
    assert reference.tolerance(None) == 1e-2
    assert reference.tolerance(2e-4) == 2e-4
    for bad in (2e-2, 0, -1e-3):
        with pytest.raises(ValueError):
            reference.tolerance(bad)
    for name in ("lm-b16-s1024", "lm-b16-s1024-dp4", "mlm-b32-s512",
                 "cls-b96-s128-pad"):
        asked = resolve.load_traffic(BENCH, name)["reference"]["loss_rel_tol"]
        assert reference.tolerance(asked) == asked
