#!/usr/bin/env python3
"""On the chip: one cell's steady step split by what the program says it is
doing: milliseconds per step in each phase (``forward``, ``backward``,
``reduce``, ``update``, ``loss_avg``, ``unscoped``) and each named kernel,
how much of each phase sits in fusions that mix phases, and each idle gap
put down to the innermost ``hvd.*`` span over it (``lib/scopes.py``).

    python3 benchmark/split.py --workload <name> [--seed 0]
        [--dump chiprun_out/<name>.split.json.gz]

It builds the cell as ``run.py`` does (no reference, no window), warms up
until two steps in a row compile nothing, traces ``TRACED_ITERATIONS``
iterations of the benchmark's loop and prints JSON lines and a table; no
result line and no metric. Scope names live in the compiled program's
metadata, which is not part of the persistent cache's key: judge them on a
fresh ``JAX_COMPILATION_CACHE_DIR``. ``--dump`` saves the events with the
labels, names and mixed fusions the split used (how the fixtures under
``tests/`` were recorded). ``--tiny`` under ``JAX_PLATFORMS=cpu`` rehearses
the path up to the trace: a CPU has no device plane to split.
"""

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main(args) -> int:
    import jax

    from benchmark.lib import cell as cell_lib, compile_info, harness, loop
    from benchmark.lib import program, scopes, trace as trace_lib

    built = cell_lib.build(
        ROOT, BENCH_DIR, args.workload, seed=args.seed, tiny=args.tiny
    )
    cell, devices, step = built.cell, built.devices, built.step
    batch = next(built.batches)
    hlo = step.lower(built.state, batch).compile().as_text()
    labels = compile_info.instruction_labels(hlo)
    kernel_names = compile_info.pallas_call_names(hlo)
    collective_names = compile_info.collective_names(hlo)
    mixed = scopes.fusion_phases(hlo)
    harness.emit(
        "split_of", workload=cell.name, seed=args.seed,
        platform=devices[0].platform, device_kind=devices[0].device_kind,
        is_rehearsal=args.tiny, compile_cache_dir=built.cache_dir,
        cache=built.counter.take(), pallas_calls=len(kernel_names),
        collectives=len(collective_names), fusions=len(mixed),
        fusions_mixing_scopes=sum(
            1 for v in mixed.values() if len(scopes.scopes_inside(v)) > 1
        ),
    )
    cell_lib.warm_up(built, batch)
    # what the program booked about its own builds so far (obs/build.py)
    harness.emit("builds", **program.step_builds())

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="bench_split_")
    try:
        with jax.profiler.trace(tmp, profiler_options=options):
            loop.run_window(
                step, built.state, built.batches,
                iterations=harness.TRACED_ITERATIONS, annotate=True,
            )
        (path,) = glob.glob(
            os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb")
        )
        events = scopes.read_xplane(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if args.dump:
        os.makedirs(os.path.dirname(args.dump) or ".", exist_ok=True)
        with gzip.open(args.dump, "wt") as f:
            json.dump({
                "events": events, "labels": labels, "mixed": mixed,
                "kernel_names": kernel_names,
                "collective_names": collective_names,
            }, f)
    host_names = sorted({
        name for plane, _, name, _, _ in events
        if not trace_lib.DEVICE_PLANE.match(plane)
    })
    if args.tiny:
        harness.emit("split_rehearsal", events=len(events),
                     host_spans=host_names)
        return 0
    result = scopes.split(
        events, labels, kernel_names=kernel_names,
        collective_names=collective_names, mixed=mixed,
    )
    summary = trace_lib.summarize(
        events, kernel_names=kernel_names, collective_names=collective_names,
    )
    busy_ms = summary.per_step_ms("busy_s")
    harness.emit(
        "split", workload=cell.name, steps=result["steps"],
        busy_ms_per_step_lib_trace=busy_ms, devices=result["devices"],
        host_spans_ms_p50={
            name: sorted(ms)[len(ms) // 2]
            for name, ms in result["host_spans_ms"].items()
        },
        host_spans=host_names,
    )
    # Where the device trace keeps each kernel's identity: the HLO
    # instruction's own name, or only the label.
    harness.emit(
        "split_names", kernel_instructions=kernel_names[:6],
        kernel_labels=[labels.get(n, "") for n in kernel_names[:6]],
        collective_labels={n: labels.get(n, "") for n in collective_names},
    )
    print(scopes.table(result, busy_ms), flush=True)
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dump", default=None)
    ap.add_argument("--tiny", action="store_true")
    sys.exit(main(ap.parse_args()))
