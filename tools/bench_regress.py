#!/usr/bin/env python
"""bench_regress — compare a fresh bench run against the checked-in baseline.

Parses the metric JSON lines out of a fresh ``bench.py`` stdout capture
and compares them against the newest checked-in ``BENCH_r*.json``
snapshot (whose ``tail`` field embeds the same line format). A metric
regresses when its fresh ``step_time_ms`` exceeds the baseline by more
than the *measured* noise: the tolerance is ``slack`` times the combined
``step_ms_spread`` of the two runs, floored at ``min_rel`` of the
baseline so a near-zero spread can't flag sub-percent jitter.

On/off pair lines (``quant_onoff``, ``fp8_onoff``, ``act_quant_onoff``,
``remat_onoff``, ...) compare the knob's ON-side step time under the
plain relative gate, and their boolean health fields (fp8 ``converged``,
act-quant ``memplan_ok``) fail the run outright when False in the fresh
capture — baseline or not. Metrics without step timing (serve/decode/
goodput lines) fall back to a plain relative check on their headline
value, where "bigger is worse" vs "bigger is better" is inferred from
the field compared.

Exit codes: 0 ok, 1 significant regression, 2 nothing comparable.

Usage::

    python bench.py | python tools/bench_regress.py --fresh -
    python tools/bench_regress.py --fresh run.log
    python tools/bench_regress.py --fresh run.log --baseline BENCH_r04.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Headline value per non-step metric family: (field, higher_is_better).
_VALUE_FIELDS = {
    "serve_latency": ("requests_per_s", True),
    "serve_decode": ("tokens_per_s", True),
    "goodput": ("fraction", True),
    "trace_onoff": ("overhead_pct", False),
}

# Boolean health gates carried by the on/off pair lines: a False in the
# FRESH record fails the run outright, baseline or not — a diverging fp8
# step or a drifted memory plan is a regression at any speed.
_GATE_FIELDS = {
    "fp8_onoff": ("converged",),
    "act_quant_onoff": ("memplan_ok",),
}


def metric_lines(text: str) -> Dict[str, dict]:
    """``{metric_name: record}`` from bench stdout. Later lines win: a
    metric printed twice keeps its last record."""
    out: Dict[str, dict] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            out[rec["metric"]] = rec
    return out


def newest_baseline(directory: str = REPO) -> Optional[str]:
    """Highest-numbered ``BENCH_r*.json`` (the snapshots are append-only
    and numbered, so lexical order on the zero-padded suffix is age)."""
    paths = [
        p for p in glob.glob(os.path.join(directory, "BENCH_r*.json"))
        if re.search(r"BENCH_r\d+\.json$", p)
    ]
    return max(paths) if paths else None


def load_records(path: str) -> Dict[str, dict]:
    """Metric records from either a raw bench stdout capture or a
    ``BENCH_r*.json`` snapshot (detected by its ``tail`` field)."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict) and "tail" in doc:
        text = doc["tail"]
    return metric_lines(text)


def compare(fresh: Dict[str, dict], base: Dict[str, dict],
            slack: float = 3.0, min_rel: float = 0.05,
            value_rel: float = 0.15) -> List[dict]:
    """One row per metric present in both runs; ``ok=False`` rows are
    significant regressions."""
    rows: List[dict] = []
    for name in sorted(fresh):
        f = fresh[name]
        for gate in _GATE_FIELDS.get(name, ()):
            if f.get(gate) is False:
                rows.append({
                    "metric": name, "field": gate,
                    "baseline": 1.0, "fresh": 0.0, "limit": 1.0,
                    "ok": False,
                })
        if name not in base:
            continue
        b = base[name]
        if "step_time_ms" in f and "step_time_ms" in b:
            spread = float(b.get("step_ms_spread", 0.0)) + float(
                f.get("step_ms_spread", 0.0)
            )
            limit = float(b["step_time_ms"]) + max(
                slack * spread, min_rel * float(b["step_time_ms"])
            )
            rows.append({
                "metric": name,
                "field": "step_time_ms",
                "baseline": float(b["step_time_ms"]),
                "fresh": float(f["step_time_ms"]),
                "limit": round(limit, 3),
                "ok": float(f["step_time_ms"]) <= limit,
            })
            continue
        if "step_ms_on" in f and "step_ms_on" in b:
            # On/off pair lines (quant_onoff, fp8_onoff, act_quant_onoff,
            # ...): the knob's ON side is the number the pair exists to
            # defend, and the pairs carry no spread field, so the plain
            # relative gate applies.
            bv, fv = float(b["step_ms_on"]), float(f["step_ms_on"])
            limit = bv * (1.0 + value_rel)
            rows.append({
                "metric": name, "field": "step_ms_on", "baseline": bv,
                "fresh": fv, "limit": round(limit, 3), "ok": fv <= limit,
            })
            continue
        field, higher_better = _VALUE_FIELDS.get(name.split("_goodput")[0],
                                                 (None, True))
        if field is None or f.get(field) is None or b.get(field) is None:
            continue
        bv, fv = float(b[field]), float(f[field])
        if higher_better:
            limit = bv * (1.0 - value_rel)
            ok = fv >= limit
        else:
            limit = bv * (1.0 + value_rel) if bv > 0 else bv + value_rel
            ok = fv <= limit
        rows.append({
            "metric": name, "field": field, "baseline": bv,
            "fresh": fv, "limit": round(limit, 3), "ok": ok,
        })
    return rows


def render(rows: List[dict], baseline_path: Optional[str]) -> str:
    lines = [f"baseline: {baseline_path or '<given records>'}"]
    for r in rows:
        verdict = "ok" if r["ok"] else "REGRESSION"
        lines.append(
            f"  {r['metric']:>42} {r['field']:>14}: "
            f"{r['baseline']:.3f} -> {r['fresh']:.3f} "
            f"(limit {r['limit']:.3f}) [{verdict}]"
        )
    bad = sum(1 for r in rows if not r["ok"])
    lines.append(
        f"{len(rows)} metric(s) compared, {bad} regression(s)"
    )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="bench_regress")
    ap.add_argument(
        "--fresh", required=True,
        help="fresh bench stdout capture ('-' reads stdin)",
    )
    ap.add_argument(
        "--baseline", default=None,
        help="baseline snapshot (default: newest BENCH_r*.json in the "
        "repo root)",
    )
    ap.add_argument("--slack", type=float, default=3.0,
                    help="spread multiples of headroom (default 3)")
    ap.add_argument("--min-rel", type=float, default=0.05,
                    help="relative tolerance floor (default 0.05)")
    ap.add_argument("--value-rel", type=float, default=0.15,
                    help="tolerance for spread-less value metrics")
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    if args.fresh == "-":
        fresh = metric_lines(sys.stdin.read())
    else:
        fresh = load_records(args.fresh)
    baseline_path = args.baseline or newest_baseline()
    if baseline_path is None:
        print("bench_regress: no BENCH_r*.json baseline found",
              file=sys.stderr)
        return 2
    base = load_records(baseline_path)
    rows = compare(fresh, base, slack=args.slack, min_rel=args.min_rel,
                   value_rel=args.value_rel)
    if not rows:
        print("bench_regress: no metrics comparable against "
              f"{baseline_path}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({
            "baseline": baseline_path,
            "rows": rows,
            "ok": all(r["ok"] for r in rows),
        }, sort_keys=True))
    else:
        print(render(rows, baseline_path))
    return 0 if all(r["ok"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
