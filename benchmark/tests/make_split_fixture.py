#!/usr/bin/env python3
"""Cut a split dump (``split.py --dump <file>``) down to three steady steps
and write it with its readings as a fixture:

    python3 benchmark/tests/make_split_fixture.py <dump.split.json.gz> <name>

Writes ``fixtures/<name>.split.json.gz`` (events, and the labels, names and
mixed fusions of the instructions that ran in them) and
``fixtures/<name>.split.expected.json``.
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.lib import scopes, trace as T  # noqa: E402

STEPS = 3
FIRST_SYNC = 4  # skip the trace's first steps


def main(dump, name):
    with gzip.open(dump, "rt") as f:
        d = json.load(f)
    sync_ends = sorted(
        s + dur for p, _, n, s, dur in d["events"]
        if n == "sync" and not T.DEVICE_PLANE.match(p)
    )
    lo, hi = sync_ends[FIRST_SYNC], sync_ends[FIRST_SYNC + STEPS]
    kept, ran = [], set()
    for plane, line, ev, start, dur in d["events"]:
        end = start + dur
        if T.DEVICE_PLANE.match(plane):
            wanted = line == T.OP_LINE or (
                line == T.ASYNC_LINE
                and T.is_collective(ev, d["collective_names"])
            )
            if wanted and end > lo and start < hi:
                kept.append([plane, line, ev, start - lo, dur])
                ran.add(ev)
        elif lo <= end <= hi:
            kept.append([plane, line, ev, start - lo, dur])
    cut = {
        "events": kept,
        "labels": {k: v for k, v in d["labels"].items() if k in ran},
        "mixed": {k: v for k, v in d["mixed"].items() if k in ran},
        "kernel_names": d["kernel_names"],
        "collective_names": d["collective_names"],
    }
    out = os.path.join(HERE, "fixtures", name)
    with gzip.open(out + ".split.json.gz", "wt") as f:
        json.dump(cut, f)
    result = scopes.split(
        cut["events"], cut["labels"], kernel_names=cut["kernel_names"],
        collective_names=cut["collective_names"], mixed=cut["mixed"],
    )
    worst = max(result["devices"], key=lambda dev: dev["busy_ms"])
    expected = {
        "recorded": "on a TPU v5 lite, PR 24, three steady steps",
        "steps": result["steps"], "devices": len(result["devices"]),
        **{k: worst[k] for k in (
            "device", "busy_ms", "phases_ms", "mixed_ms", "mixed_with_ms",
            "kernels_ms", "collectives_ms", "idle_ms_by_span",
        )},
    }
    with open(out + ".split.expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected)[:900],
          os.path.getsize(out + ".split.json.gz"))


if __name__ == "__main__":
    main(*sys.argv[1:3])
