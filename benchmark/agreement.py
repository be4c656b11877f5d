#!/usr/bin/env python3
"""How far the system's first losses lie from the plain reference's, over
many seeds in one process: what a traffic file's ``reference.loss_rel_tol``
and ``reference.steps`` are set from. A run of ``run.py`` gives one sample
and costs a whole set-up; this builds the step and the reference once and
then spends a few seconds a seed.

    python3 benchmark/agreement.py --workload <name> --seeds 36 --steps 5

``--frozen n`` also prints, for the first n seeds, how far the updates move
the reference's loss (against the same batches at the initial
parameters): an update the comparison could not see guards nothing.
``--tiny`` under ``JAX_PLATFORMS=cpu`` rehearses the path. It prints JSON
lines, no result line and no metric.
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main(args) -> int:
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.lib import data as data_lib, harness, reference, resolve
    from benchmark.lib import stats

    cell = harness.load_cell(ROOT, BENCH_DIR, args.workload)
    if args.tiny:
        cell = harness.tiny(cell)
    devices, _ = harness.pick_devices(jax, cell.chips, rehearsal=args.tiny)

    import horovod_tpu as hvd
    from horovod_tpu.parallel import dp
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    hvd.init(devices)
    sharding = NamedSharding(hvd.mesh(), P(hvd.WORLD_AXIS))
    traffic = cell.traffic
    family = resolve.load_family(BENCH_DIR, traffic["family"]).build(
        cell.config, traffic
    )
    step, wrapped, optimizer = harness.build_step(cell, family, hvd, dp, optax)
    ref = traffic["reference"]
    steps = args.steps or ref["steps"]
    tol = reference.tolerance(ref.get("loss_rel_tol"))
    plain = reference.make_reference(
        family.reference_loss, optimizer, micro_batch=ref["micro_batch"]
    )
    frozen = reference.make_reference(
        family.reference_loss, optax.set_to_zero(),
        micro_batch=ref["micro_batch"],
    )
    harness.emit("agreement_of", workload=cell.name,
                 optimizer=traffic["optimizer"], steps=steps, tolerance=tol,
                 platform=devices[0].platform, is_rehearsal=args.tiny)

    rel = []
    for i in range(args.seeds):
        seed = args.first_seed + i
        key = jax.random.PRNGKey(seed)
        pool = data_lib.make_pool(
            traffic["data"], vocab_size=family.vocab_size,
            global_batch=traffic["per_chip_batch"] * cell.chips,
            seq_len=traffic["seq_len"], n_batches=steps, seed=seed,
        )
        state = dp.init_state(family.init_params(key), wrapped)
        system = []
        for batch in pool:
            state, loss = step(state, jax.device_put(batch, sharding))
            system.append(float(loss))
        reference.release(state)  # the reference gets the device
        theirs = plain(family.init_params(key), pool)
        found = reference.compare(system, theirs, tol)
        rel.append(found["rel_diff"])
        extra = {}
        if i < args.frozen:
            still = frozen(family.init_params(key), pool)
            extra["updates_move_the_loss_by"] = [
                abs(a - b) / abs(b) for a, b in zip(theirs, still)
            ]
        harness.emit("agreement_seed", seed=seed, system=system,
                     reference=theirs, rel_diff=found["rel_diff"],
                     agree=found["agree"], moments=plain.phase["moments"],
                     **extra)

    by_step = list(zip(*rel))
    harness.emit(
        "agreement", seeds=args.seeds, tolerance=tol,
        rel_diff_by_step=[
            {"p50": stats.percentile(list(xs), 50),
             "p90": stats.percentile(list(xs), 90), "max": max(xs)}
            for xs in by_step
        ],
        seeds_over_tolerance_by_step=[
            sum(1 for x in xs if x > tol) for xs in by_step
        ],
    )
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=36)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=0,
                    help="steps compared (default: the traffic file's)")
    ap.add_argument("--frozen", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    sys.exit(main(ap.parse_args()))
