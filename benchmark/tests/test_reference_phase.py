"""The reference has the device to itself: what it donates, where its
optimizer state waits, what it leaves behind, and that none of that moves a
loss (PR 26)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import optax
import pytest

from benchmark.lib import data, reference, resolve

from conftest import ROOT

BENCH = os.path.join(ROOT, "benchmark")
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
CELLS = [w["name"] for w in resolve.load_manifest(ROOT)["workloads"]]


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
    ref = traffic["reference"]
    pool = data.make_pool(
        traffic["data"], vocab_size=family.vocab_size,
        global_batch=traffic["per_chip_batch"] * w["chips"],
        seq_len=traffic["seq_len"], n_batches=ref["steps"], seed=0,
    )
    return family, pool, resolve.resolve_optimizer(
        traffic["optimizer"], optax
    ), ref


def test_nothing_the_reference_allocated_stays_live():
    family, pool, optimizer, ref = _tiny("gpt2-small.b16-s1024")
    losses = reference.make_reference(
        family.reference_loss, optimizer, micro_batch=ref["micro_batch"]
    )
    key = jax.random.PRNGKey(0)
    before = {id(x) for x in jax.live_arrays()}
    params = family.init_params(key)
    out = losses(params, pool)
    assert len(out) == ref["steps"] and all(np.isfinite(out))
    assert all(x.is_deleted() for x in jax.tree.leaves(params))  # consumed
    assert {id(x) for x in jax.live_arrays()} <= before
    assert losses.phase["moments"] == "device"
    assert losses.phase["param_bytes"] == 4 * sum(
        x.size for x in jax.tree.leaves(params)
    )


def test_add_and_apply_donate_what_they_can_give_back():
    family, pool, optimizer, _ = _tiny("bert-base.mlm-b32-s512")
    params = family.init_params(jax.random.PRNGKey(0))
    opt_state = optimizer.init(params)
    acc, grads = reference.add.lower(params, params).args_info[0]
    assert all(a.donated for a in jax.tree.leaves(acc))
    assert not any(g.donated for g in jax.tree.leaves(grads))
    apply = reference.make_apply(optimizer)
    p, s, a, n = apply.lower(
        params, opt_state, params, np.float32(2)
    ).args_info[0]
    assert all(x.donated for x in jax.tree.leaves((p, s)))
    # no output can alias the accumulator: the caller releases it instead
    assert not any(x.donated for x in jax.tree.leaves((a, n)))
    # and the call consumes them: the buffers are gone afterwards
    apply(params, opt_state, jax.tree.map(np.zeros_like, params),
          np.float32(2))
    assert all(x.is_deleted() for x in jax.tree.leaves(params))
    assert all(x.is_deleted() for x in jax.tree.leaves(opt_state))


def test_the_bytes_decide_where_the_moments_wait():
    plan = lambda limit: reference.plan_phase(  # noqa: E731
        param_bytes=4, state_bytes=8, grad_plan_bytes=10, bytes_limit=limit
    )
    # 4 (accumulator) + 8 + 10 = 22 with the state, 14 without, update 16
    assert plan(None)["moments"] == "device"
    assert plan(22 / reference.HEADROOM)["moments"] == "device"
    assert plan(22 / reference.HEADROOM)["planned_peak_bytes"] == 22
    assert plan(21 / reference.HEADROOM)["moments"] == "host"
    assert plan(16 / reference.HEADROOM)["planned_peak_bytes"] == 16
    assert plan(15 / reference.HEADROOM)["moments"] == "nowhere"
    # a gradient program larger than the update decides alone
    big = reference.plan_phase(param_bytes=4, state_bytes=8,
                               grad_plan_bytes=30, bytes_limit=40)
    assert (big["moments"], big["planned_peak_bytes"]) == ("host", 34)


@pytest.mark.parametrize("workload", CELLS)
def test_moments_on_the_host_give_the_same_losses_bit_for_bit(workload):
    family, pool, optimizer, ref = _tiny(workload)
    key = jax.random.PRNGKey(3)

    def run(bytes_limit):
        losses = reference.make_reference(
            family.reference_loss, optimizer,
            micro_batch=ref["micro_batch"],
            bytes_limit=lambda: bytes_limit,
        )
        return losses(family.init_params(key), pool), losses.phase

    on_device, phase = run(None)
    assert phase["moments"] == "device"
    # a limit the planned peak does not fit, and the peak without the state
    # does: passed as bytes, the way the chip's own limit arrives
    limit = (phase["planned_peak_bytes"] - 1) / reference.HEADROOM
    on_host, phase = run(limit)
    assert phase["moments"] == "host"
    assert on_host == on_device  # floats compared exactly
    with pytest.raises(MemoryError):
        run(phase["param_bytes"])
    assert not [x for x in jax.live_arrays() if x.nbytes > 64]


@pytest.mark.parametrize("workload", CELLS)
def test_tiny_rehearsal_prints_the_parents_reference_losses(workload):
    """The whole of ``run.py``'s path at the ``tiny`` sizes, in a process of
    its own: the reference now runs once the window has closed and draws
    the parameters again from the key, and its losses are those recorded at
    the parent of PR 26 (a CPU's bits may differ between machines in the
    last place, hence 1e-6; on the recording machine they are equal)."""
    with open(os.path.join(FIXTURES, "tiny_reference_losses.json")) as f:
        recorded = json.load(f)["losses"][workload]
    chips = resolve.find_workload(resolve.load_manifest(ROOT), workload)[
        "chips"
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "tiny",
         "--workload", workload, "--seed", "0", "--seconds", "0.5"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    kinds = [x["line"] for x in lines]
    # the order of a run: the reference comes after the window
    assert kinds.index("window") < kinds.index("reference") < (
        kinds.index("agreement")
    )
    ref = lines[kinds.index("reference")]
    assert ref["losses"] == pytest.approx(recorded, rel=1e-6)
    assert ref["moments"] == "device" and ref["param_bytes"] > 0
    assert ref["live_arrays_after"] <= ref["live_arrays_before"]
    assert lines[kinds.index("agreement")]["agree"]
    assert lines[-1]["line"] == "rehearsal" and lines[-1]["correct"]


_BROKEN = """
import sys, time
sys.path.insert(0, {root!r})
from horovod_tpu.parallel import dp
from benchmark.lib import harness

real = dp.make_train_step
def broken(*args, **kwargs):
    step, wrapped = real(*args, **kwargs)
    class Altered:  # the loss altered where the timed path produces it
        def __call__(self, state, batch):
            state, loss = step(state, batch)
            return state, loss * {factor}
        def __getattr__(self, name):
            return getattr(step, name)
    return Altered(), wrapped
dp.make_train_step = broken
cell = harness.tiny(harness.load_cell({root!r}, {bench!r}, {workload!r}))
record = harness.measure(cell, bench_dir={bench!r}, seed=0, seconds=0.3,
                         traced=False, t_start=time.perf_counter(),
                         rehearsal=True)
line = harness.result_line(record, {bench!r}, traced=False)
assert list(line)[-1] == "compared", list(line)
print("CORRECT", line["correct"], sorted(
    k for k, (v, limit) in line["compared"].items()
    if k.startswith("loss_rel_diff") and v > limit))
"""


@pytest.mark.parametrize("factor,correct", [(1.0, True), (1.05, False)])
def test_a_broken_timed_path_comes_out_not_correct(factor, correct):
    """The rest of a run without the look for a chip (a rehearsal at the
    ``tiny`` sizes), with the step's loss altered underneath by 5%:
    ``correct`` is false and ``compared`` names the steps over their limit;
    with the factor 1.0 the same wrapper passes."""
    done = subprocess.run(
        [sys.executable, "-c", _BROKEN.format(
            root=ROOT, bench=BENCH, workload="bert-base.mlm-b32-s512",
            factor=factor,
        )],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    verdict = [x for x in done.stdout.splitlines() if x.startswith("CORRECT")]
    assert verdict == [
        f"CORRECT {correct} "
        + ("[]" if correct else str([f"loss_rel_diff_step{i}" for i in (1, 2, 3)]))
    ]
