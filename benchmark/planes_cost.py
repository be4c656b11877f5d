#!/usr/bin/env python3
"""On the chip: what the program's own instrumentation costs when it is on.
One process builds the cell as ``run.py`` does, warms up, and then measures
windows of the benchmark's loop alternately with every plane off and with
the metrics and trace planes on (``hvd.obs.enable()`` +
``hvd.obs.trace.enable()``: what ``HVDTPU_METRICS=1 HVDTPU_TRACE=1`` arm;
the step's wrapper checks per call), so both readings share one machine,
one compile and one warm-up.

    python3 benchmark/planes_cost.py --workload <name> [--seconds 20]
        [--pairs 3] [--seed 0]

Prints one JSON line per window (median and 90th-percentile gap between the
loop's stamps, dispatch and input-wait medians) and a last line with the
medians of each side and their ratio. No result line and no metric. It
uses nothing newer than the program's ``enable()``/``disable()``, so it
runs on an older checkout with this file, ``lib/cell.py`` and
``lib/program.py`` copied in.
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main(args) -> int:
    from benchmark.lib import cell as cell_lib, harness, loop, program, stats

    built = cell_lib.build(
        ROOT, BENCH_DIR, args.workload, seed=args.seed, tiny=args.tiny
    )
    hvd, counter = built.hvd, built.counter
    cell_lib.warm_up(built)
    # what the program booked about its own builds (obs/build.py; empty
    # on a checkout that does not count)
    harness.emit("builds", **program.step_builds())

    trace_dir = os.path.join(ROOT, "chiprun_out", "planes_cost_trace")
    medians = {"off": [], "on": []}
    for i in range(2 * args.pairs):
        side = "on" if i % 2 else "off"
        if side == "on":
            hvd.obs.enable()
            hvd.obs.trace.enable(directory=trace_dir)
        built.state, win = loop.run_window(
            built.step, built.state, built.batches, seconds=args.seconds
        )
        if side == "on":
            hvd.obs.disable()
            hvd.obs.trace.disable()
        gaps = stats.gaps_ms(win["stamps"])
        medians[side].append(stats.percentile(gaps, 50))
        # a rehearsal on the CPU keeps the counts and prints no time
        times = {} if args.tiny else {
            "step_ms_p50": medians[side][-1],
            "step_ms_p90": stats.percentile(gaps, 90),
            "dispatch_ms_p50": stats.percentile(
                [x * 1e3 for x in win["dispatch_s"]], 50),
            "input_wait_ms_p50": stats.percentile(
                [x * 1e3 for x in win["input_wait_s"]], 50),
        }
        harness.emit("planes_window", planes=side, steps=len(gaps),
                     compiles=counter.take(), **times)
    off = stats.percentile(medians["off"], 50)
    on = stats.percentile(medians["on"], 50)
    harness.emit(
        "planes_cost", workload=built.cell.name,
        platform=built.devices[0].platform,
        is_rehearsal=args.tiny, pairs=args.pairs,
        **({} if args.tiny else {
            "step_ms_p50_off": off, "step_ms_p50_on": on,
            "on_over_off": on / off - 1.0,
        }),
    )
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    sys.exit(main(ap.parse_args()))
