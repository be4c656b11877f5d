#!/usr/bin/env python3
"""Controls: builds that are wrong on purpose, put through the comparison
that decides ``correct`` at the cell's own tolerance. A comparison that
passes them guards nothing; a traffic file's ``reference.loss_rel_tol`` is
sound where the unaltered pair passes on every seed and each control fails.

    python3 benchmark/controls.py --workload <name> --seeds 2

The cell's family file offers ``controls(config, traffic) -> {name:
Family}``: ``"none"`` is the sound family, every other has ONE side
altered (the program's ``loss_fn`` or the ``reference_loss``) and shares
the other with it; a family without the function has no controls. For
each seed this runs the sound program and the sound reference once
(``control`` "none", which has to agree), then each altered side against
the other's sound losses, through ``lib/reference.compare`` and the
traffic file's tolerance, as ``lib/harness.measure`` does. ``--tiny`` under
``JAX_PLATFORMS=cpu`` rehearses the path. It prints JSON lines, no result
line and no metric; the last line says which controls every seed refused.
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
    module = resolve.load_family(BENCH_DIR, traffic["family"])
    if not hasattr(module, "controls"):
        raise SystemExit(f"family {traffic['family']} offers no controls")
    altered = module.controls(cell.config, traffic)
    sound = altered.pop("none")
    if args.controls:
        altered = {name: altered[name] for name in args.controls.split(",")}
    ref = traffic["reference"]
    tol = reference.tolerance(ref.get("loss_rel_tol"))
    optimizer = resolve.resolve_optimizer(traffic["optimizer"], optax)
    harness.emit("controls_of", workload=cell.name, tolerance=tol,
                 optimizer=traffic["optimizer"], steps=ref["steps"],
                 controls=sorted(altered), platform=devices[0].platform,
                 is_rehearsal=args.tiny)

    def program(family):
        step, wrapped = dp.make_train_step(
            family.loss_fn, optimizer, **resolve.resolve_step_kwargs(
                traffic.get("step_kwargs", {}), hvd
            ),
        )

        def losses(key, pool):
            state = dp.init_state(sound.init_params(key), wrapped)
            out = []
            for batch in pool:
                state, loss = step(state, jax.device_put(batch, sharding))
                out.append(float(loss))
            reference.release(state)  # the next program gets the device
            return out

        return losses

    def plain(family):
        losses = reference.make_reference(
            family.reference_loss, optimizer, micro_batch=ref["micro_batch"]
        )
        return lambda key, pool: losses(sound.init_params(key), pool)

    # one side of each control is the sound one: built once, run once a seed
    sides = {"none": (program(sound), plain(sound))}
    for name, family in altered.items():
        sides[name] = (
            sides["none"][0] if family.loss_fn is sound.loss_fn
            else program(family),
            sides["none"][1] if family.reference_loss is sound.reference_loss
            else plain(family),
        )
    refused = {name: [] for name in sides}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        key = jax.random.PRNGKey(seed)
        pool = data_lib.make_pool(
            traffic["data"], vocab_size=sound.vocab_size,
            global_batch=traffic["per_chip_batch"] * cell.chips,
            seq_len=traffic["seq_len"], n_batches=ref["steps"], seed=seed,
        )
        done = {}
        for name, pair in sides.items():
            system, theirs = (
                done.setdefault(side, side(key, pool)) for side in pair
            )
            found = reference.compare(system, theirs, tol)
            refused[name].append(not found["agree"])
            harness.emit("control", control=name, seed=seed, **found)
    harness.emit(
        "controls", seeds=args.seeds, tolerance=tol,
        sound_pair_agrees_on_every_seed=not any(refused.pop("none")),
        refused_on_every_seed={n: all(r) for n, r in refused.items()},
        seeds_refused={n: sum(r) for n, r in refused.items()},
    )
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--controls", default="",
                    help="comma-separated names (default: all the family's)")
    ap.add_argument("--tiny", action="store_true")
    sys.exit(main(ap.parse_args()))
