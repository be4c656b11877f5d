#!/usr/bin/env python3
"""Rehearsals that cost no chip time. Nothing printed here is a
measurement: no line carries a time, a rate or a share under a metric's
name, and there is no result line.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py tiny --workload <name>
        the whole of run.py's path (init, step, prefetch, reference,
        warm-up, window, result assembly) at the ``tiny`` sizes of the
        cell's files, on the CPU; for a four-chip cell add
        XLA_FLAGS=--xla_force_host_platform_device_count=4

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py compile --workload <name>
        the cell's real step and its reference's gradient program, compiled
        for a DESCRIBED v5e:2x2 (no chip attached): what the chip's compiler
        refuses, the planned bytes, the Pallas calls and the collectives, and
        the reference phase's planned peak (parameters, optimizer state and
        accumulator beside the gradient program) with whether it fits the
        chip with the optimizer state on the device, on the host, or not at
        all: a cell is sized here, with no chip
"""

import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def rehearse_tiny(args) -> int:
    from benchmark.lib import harness

    cell = harness.tiny(harness.load_cell(ROOT, BENCH_DIR, args.workload))
    record = harness.measure(
        cell, bench_dir=BENCH_DIR, seed=args.seed, seconds=args.seconds,
        traced=False, t_start=time.perf_counter(), rehearsal=True,
    )
    # Assemble both result lines to exercise the readers, print only the
    # names: a CPU's numbers are not measurements.
    e2e = harness.result_line(record, BENCH_DIR, traced=False)
    layers = harness.result_line(record, BENCH_DIR, traced=True)
    harness.emit(
        "rehearsal", correct=record["correct"], reasons=record["reasons"],
        attempted=record["attempted"], failed=record["failed"],
        end_to_end_names=sorted(e2e["metrics"]),
        per_layer_names_without_trace=sorted(layers["metrics"]),
    )
    return 0 if record["correct"] else 1


def rehearse_compile(args) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel import dp

    from benchmark.lib import compile_info, harness, reference, resolve
    from benchmark.lib.peaks import peak_for

    cell = harness.load_cell(ROOT, BENCH_DIR, args.workload)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    hvd.init(topo.devices[:cell.chips])
    mesh = hvd.mesh()
    traffic = cell.traffic
    family = resolve.load_family(BENCH_DIR, traffic["family"]).build(
        cell.config, traffic
    )
    step, wrapped, optimizer = harness.build_step(
        cell, family, hvd, dp, optax
    )
    key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)
    params = jax.eval_shape(family.init_params, key)
    state = jax.eval_shape(lambda p: dp.init_state(p, wrapped), params)

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
            tree,
        )

    from benchmark.lib import data as data_lib

    global_batch = traffic["per_chip_batch"] * cell.chips
    host = data_lib.make_pool(
        traffic["data"], vocab_size=family.vocab_size,
        global_batch=global_batch, seq_len=traffic["seq_len"], n_batches=1,
        seed=0,
    )[0]
    batch = placed(host, P(hvd.WORLD_AXIS))
    counter = compile_info.CompileCounter()
    built = compile_info.lower_and_compile(
        lambda: step.lower(placed(state, P()), batch), counter
    )
    harness.emit(
        "described_compile", workload=cell.name, chips=cell.chips,
        topology="v5e:2x2 (described, nothing ran)", plan=built["plan"],
        pallas_calls=built["pallas_calls"], collectives=built["collectives"],
        expect=traffic.get("expect", {}),
    )
    micro = traffic["reference"]["micro_batch"]
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    on_one = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
    )
    ref = jax.jit(jax.value_and_grad(family.reference_loss)).lower(
        on_one(params),
        on_one({k: v[:micro] for k, v in host.items()}),
    ).compile()
    ref_plan = compile_info.planned_bytes(ref.memory_analysis())
    harness.emit(
        "described_compile_reference", micro_batch=micro, plan=ref_plan
    )
    # What the reference phase will hold beside that program (parameters,
    # optimizer state, accumulator), and where the state will wait: the
    # decision run.py takes from the same bytes on the chip.
    limit = peak_for(topo.devices[0].device_kind).usable_hbm_bytes
    phase = reference.plan_phase(
        param_bytes=reference.tree_bytes(params),
        state_bytes=reference.tree_bytes(
            jax.eval_shape(optimizer.init, params)
        ),
        grad_plan_bytes=ref_plan["peak_bytes"], bytes_limit=limit,
    )
    harness.emit(
        "described_reference_phase", **phase,
        fits={"device": "with the optimizer state on the device",
              "host": "only with the optimizer state waiting on the host",
              "nowhere": "not at all"}[phase["moments"]],
        system_step_fits=built["plan"]["peak_bytes"] <= limit,
    )
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("tiny", "compile"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    a = ap.parse_args()
    sys.exit(rehearse_tiny(a) if a.mode == "tiny" else rehearse_compile(a))
