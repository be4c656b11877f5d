#!/usr/bin/env python3
"""Cut a trace dump (``run.py --trace 1 --trace-dump <file>``) down to three
steady steps and write it with its readings as a fixture:

    python3 benchmark/tests/make_fixture.py <dump.events.json.gz> \
        <run's stdout> <fixture name>

The run's stdout gives the kernel and collective names of the compiled step
(its ``trace_inventory`` line). Writes ``fixtures/<name>.events.json.gz``
and ``fixtures/<name>.expected.json``.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.lib import trace as T  # noqa: E402

STEPS = 3
FIRST_SYNC = 4  # skip the trace's first steps


def main(dump, stdout_path, name):
    names = None
    with open(stdout_path) as f:
        for line in f:
            if line.startswith("{") and '"trace_inventory"' in line:
                names = json.loads(line)
    events = T.load_events(dump)
    sync_ends = sorted(
        s + d for p, _, n, s, d in events
        if n == "sync" and not T.DEVICE_PLANE.match(p)
    )
    lo, hi = sync_ends[FIRST_SYNC], sync_ends[FIRST_SYNC + STEPS]
    kept = []
    for plane, line, ev, start, dur in events:
        end = start + dur
        ev = T.instruction_name(ev)
        if T.DEVICE_PLANE.match(plane):
            wanted = line == T.OP_LINE or (
                line == T.ASYNC_LINE
                and T.is_collective(ev, names["collective_names"])
            )
            if wanted and end > lo and start < hi:
                kept.append([plane, line, ev, start - lo, dur])
        elif lo <= end <= hi:
            kept.append([plane, line, ev, start - lo, dur])
    out = os.path.join(HERE, "fixtures", name)
    T.save_events(kept, out + ".events.json.gz")
    s = T.summarize(
        kept, kernel_names=names["kernel_names"],
        collective_names=names["collective_names"],
    )
    expected = {
        "recorded": "on a TPU v5 lite, PR 23, three steady steps",
        "kernel_names": names["kernel_names"],
        "collective_names": names["collective_names"],
        "steps": s.steps, "devices": len(s.devices),
        "has_kernels": s.worst("kernels_s") > 0,
        "has_collectives": s.worst("collective_s") > 0,
    }
    for field in ("busy_s", "kernels_s", "collective_s",
                  "collective_exposed_s", "other_s"):
        expected[field] = s.worst(field)
    with open(out + ".expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected)[:600], os.path.getsize(out + ".events.json.gz"))


if __name__ == "__main__":
    main(*sys.argv[1:4])
