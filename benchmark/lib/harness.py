"""One cell, measured: the only module of the benchmark that calls the
program under test.

From the program it takes ``hvd.init``, ``dp.make_train_step``,
``dp.init_state``, ``hvd.prefetch_to_device``, the model classes (through
the family file) and ``enable_compile_cache``. Data, clock, percentiles,
trace reduction, peaks, FLOP rules, the reference and the comparison are
the benchmark's own (``benchmark/lib``).

Every line printed is one JSON object. The earlier lines carry
``"line": <kind>``; the last is the contract's result line.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import compile_info, data as data_lib, loop, reference, stats
from . import resolve, trace as trace_lib
from .peaks import UnknownChip, peak_for

WARMUP_MIN_STEPS = 3
WARMUP_MAX_STEPS = 12
QUIET_STEPS = 2  # consecutive steps without a compilation end the warm-up
TRACED_ITERATIONS = 12  # 12 syncs bound 11 whole steady steps
MIN_STEPS = 100  # so that ten gaps lie beyond the 90th percentile


class Refused(Exception):
    """The run cannot be a measurement (no chip, unknown chip, too few
    chips): non-zero exit, no result line."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    manifest: dict


# What a rehearsal on the CPU (benchmark/rehearse.py) may not print: every
# time, rate and share. Its lines keep the counts.
_MEASURED_ONLY = frozenset({
    "lower_s", "compile_s", "setup_s", "seconds", "step_ms", "dispatch_ms",
    "input_wait_ms", "mfu", "whole_window_tokens_per_s_per_chip",
})


def emit(kind: str, *, rehearsal: bool = False, **fields) -> None:
    if rehearsal:
        fields = {k: v for k, v in fields.items() if k not in _MEASURED_ONLY}
    print(json.dumps({"line": kind, **fields}), flush=True)


def load_cell(root: str, bench_dir: str, workload: str) -> Cell:
    manifest = resolve.load_manifest(root)
    entry = resolve.find_workload(manifest, workload)
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=resolve.load_config(root, manifest, entry["config"]),
        traffic=resolve.load_traffic(bench_dir, entry["traffic"]),
        manifest=manifest,
    )


def tiny(cell: Cell) -> Cell:
    """The cell at its files' ``tiny`` sizes: for rehearsals on the CPU."""
    return dataclasses.replace(
        cell,
        config={**cell.config, **cell.config.get("tiny", {})},
        traffic={**cell.traffic, **cell.traffic.get("tiny", {})},
    )


def pick_devices(jax, chips: int, *, rehearsal: bool):
    devices = jax.devices()
    if rehearsal:
        if len(devices) < chips:
            raise Refused(f"rehearsal needs {chips} devices, have {len(devices)}")
        return devices[:chips], None
    if devices[0].platform != "tpu":
        raise Refused(
            f"no TPU: jax.devices()[0].platform is {devices[0].platform!r}; "
            "the benchmark never measures on another platform"
        )
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, jax found {len(devices)}")
    try:
        return devices[:chips], peak_for(devices[0].device_kind)
    except UnknownChip as e:
        raise Refused(str(e)) from None


def build_step(cell: Cell, family, framework, dp, optax):
    optimizer = resolve.resolve_optimizer(cell.traffic["optimizer"], optax)
    step, wrapped = dp.make_train_step(
        family.loss_fn, optimizer,
        **resolve.resolve_step_kwargs(
            cell.traffic.get("step_kwargs", {}), framework
        ),
    )
    return step, wrapped, optimizer


def memory_peaks(devices) -> list:
    out = []
    for d in devices:
        s = d.memory_stats()
        out.append(s.get("peak_bytes_in_use") if s else None)
    return out


def measure(cell: Cell, *, bench_dir: str, seed: int, seconds: float,
            traced: bool, t_start: float, rehearsal: bool = False,
            trace_dump: str | None = None) -> dict:
    """Runs the cell and returns the record the result line is made from.
    ``t_start`` is ``time.perf_counter()`` at process start."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices, peak = pick_devices(jax, cell.chips, rehearsal=rehearsal)

    import horovod_tpu as hvd
    from horovod_tpu.parallel import dp
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # Every program goes to the persistent cache, however quickly it
    # compiled, so a warm run reads all of them and reports no miss.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = compile_info.CompileCounter()

    hvd.init(devices)
    mesh = hvd.mesh()
    emit(
        "env", workload=cell.name, seed=seed, is_rehearsal=rehearsal,
        jax=jax.__version__, platform=devices[0].platform,
        device_kind=devices[0].device_kind, chips=cell.chips,
        world=hvd.size(), compile_cache_dir=cache_dir,
    )
    if hvd.size() != cell.chips:
        raise RuntimeError(f"hvd.size() is {hvd.size()}, want {cell.chips}")

    traffic = cell.traffic
    family = resolve.load_family(bench_dir, traffic["family"]).build(
        cell.config, traffic
    )
    key = jax.random.PRNGKey(seed)
    params = family.init_params(key)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    flops_per_token = family.flops_per_token(params)
    step, wrapped, optimizer = build_step(cell, family, hvd, dp, optax)
    state = dp.init_state(params, wrapped)

    global_batch = traffic["per_chip_batch"] * cell.chips
    seq_len = traffic["seq_len"]
    tokens_per_step = global_batch * seq_len
    pool = data_lib.make_pool(
        traffic["data"], vocab_size=family.vocab_size,
        global_batch=global_batch, seq_len=seq_len,
        n_batches=traffic["pool_batches"], seed=seed,
    )
    # hvd.ShardedBatches shards by rank for one process per chip; a cell is
    # one process over all its chips, so the benchmark cycles its own pool
    # and lets the program's prefetch stage and shard it.
    batches = hvd.prefetch_to_device(
        data_lib.cycle(pool),
        sharding=NamedSharding(mesh, P(hvd.WORLD_AXIS)),
    )
    first = next(batches)
    shard_devices = {
        s.device for s in first["tokens"].addressable_shards
    }

    built = compile_info.lower_and_compile(
        lambda: step.lower(state, first), counter
    )
    emit(
        "compile", rehearsal=rehearsal, lower_s=built["lower_s"],
        compile_s=built["compile_s"],
        cache=built["cache"], plan=built["plan"],
        pallas_calls=built["pallas_calls"], collectives=built["collectives"],
        n_params=n_params, flops_per_token=flops_per_token,
        global_batch=global_batch, seq_len=seq_len,
        padding_share=data_lib.padding_share(pool),
    )

    # -- warm-up: until nothing compiles; the first losses are compared with
    # the reference's once the window has closed
    warm_losses, compiles = [], []
    batch, quiet = first, 0
    while True:
        counter.take()
        state, loss = step(state, batch)
        loss.block_until_ready()
        compiles.append(counter.take())
        warm_losses.append(float(loss))
        quiet = quiet + 1 if compiles[-1]["compile_requests"] == 0 else 0
        if quiet >= QUIET_STEPS and len(warm_losses) >= max(
            WARMUP_MIN_STEPS, traffic["reference"]["steps"]
        ):
            break
        if len(warm_losses) >= WARMUP_MAX_STEPS:
            raise RuntimeError(
                f"still compiling after {WARMUP_MAX_STEPS} steps: {compiles}"
            )
        batch = next(batches)
    warmup_recompiles = sum(c["compile_requests"] for c in compiles[1:])
    emit("warmup", steps=len(warm_losses), losses=warm_losses,
         compiles_per_step=compiles, recompiles_after_first=warmup_recompiles)

    # -- the window -------------------------------------------------------
    counter.take()
    setup_cache = dict(counter.since_start)
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    state, win = loop.run_window(
        step, state, batches, seconds=seconds,
        min_steps=0 if rehearsal else MIN_STEPS,
    )
    in_window = counter.take()
    losses = [float(x) for x in jax.device_get(win.pop("losses"))]
    failed = sum(1 for x in losses if not math.isfinite(x))
    rate = stats.throughput(win["stamps"], tokens_per_step, cell.chips)
    gaps = stats.gaps_ms(win["stamps"])
    runtime_peaks = memory_peaks(devices)
    plan_peak = built["plan"]["peak_bytes"]
    emit(
        "window", rehearsal=rehearsal, setup_s=setup_s,
        setup_cache=setup_cache, dispatched=win["dispatched"],
        steps=rate["steps"], seconds=rate["seconds"],
        whole_window_tokens_per_s_per_chip=rate[
            "whole_window_tokens_per_s_per_chip"
        ],
        step_ms=stats.quartiles(gaps),
        dispatch_ms=stats.quartiles([x * 1e3 for x in win["dispatch_s"]]),
        input_wait_ms=stats.quartiles([x * 1e3 for x in win["input_wait_s"]]),
        loss_first=losses[0], loss_last=losses[-1],
        loss_every_10th=losses[::10][:64], non_finite=failed,
        compiles_in_window=in_window,
        mfu=(None if peak is None else
             rate["tokens_per_s_per_chip"] * flops_per_token
             / peak.bf16_flops),
        peak_bytes_in_use=runtime_peaks, planned_peak_bytes=plan_peak,
    )

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind, "count": cell.chips,
        # The plan of the compiled step or the runtime's high-water mark,
        # whichever is larger: on this runtime the counter sees live arrays
        # and not the program's temporaries (PERF.md). Read before the
        # reference runs: a process's peak never falls again.
        "memory_peak_bytes": max(
            [plan_peak] + [p for p in runtime_peaks if p]
        ),
    }
    trace = None
    if traced and not rehearsal:
        state, trace = traced_steps(
            jax, step, state, batches, built, gaps, trace_dump
        )
        device["busy_s"] = trace.busy_s_mean
        device["window_s"] = trace.window_s

    # -- the plain reference, with the device to itself -------------------
    del first, batch, batches
    reference.release(state)
    ref = traffic["reference"]
    ref_losses = reference_phase(
        jax, family, optimizer, key, pool[:ref["steps"]],
        ref["micro_batch"], counter, devices[0], rehearsal=rehearsal,
    )
    agreement = reference.compare(
        warm_losses[:ref["steps"]], ref_losses,
        reference.tolerance(ref.get("loss_rel_tol")),
    )
    emit("agreement", **agreement)

    # -- correct: each number compared, its limit, and whether it holds ----
    expect = traffic.get("expect", {})
    tol = agreement["tolerance_rel"]
    checks = [
        (f"loss_rel_diff_step{i + 1}", x, tol, x <= tol)
        for i, x in enumerate(agreement["rel_diff"])
    ]
    n_compiled = in_window["compile_requests"]
    checks += [
        ("losses_compared_exactly", len(agreement["rel_diff"]), ref["steps"],
         len(agreement["rel_diff"]) == ref["steps"]),
        ("non_finite_losses", failed, 0, not failed),
        ("compiles_in_window", n_compiled, 0, not n_compiled),
        ("batch_shard_devices_exactly", len(shard_devices), cell.chips,
         len(shard_devices) == cell.chips),
    ]
    if not rehearsal:
        checks.append(("steps_completed_at_least", rate["steps"], MIN_STEPS,
                       rate["steps"] >= MIN_STEPS))
    if not rehearsal and "pallas_calls" in expect:
        checks.append(("pallas_calls_exactly", built["pallas_calls"],
                       expect["pallas_calls"],
                       built["pallas_calls"] == expect["pallas_calls"]))
    if expect.get("all_reduce"):
        n_all_reduce = built["collectives"]["compiled"]["all-reduce"]
        checks.append(("all_reduces_at_least", n_all_reduce, 1,
                       n_all_reduce >= 1))
    reasons = [f"{name}: {value}, limit {limit}"
               for name, value, limit, holds in checks if not holds]
    compared = {name: [value, limit] for name, value, limit, _ in checks}

    return {
        "correct": not reasons, "reasons": reasons, "compared": compared,
        "attempted": win["dispatched"], "failed": failed,
        "cell": cell, "family": family, "peak": peak, "built": built,
        "setup_s": setup_s, "rate": rate, "gaps_ms": gaps, "window": win,
        "warmup_recompiles": warmup_recompiles,
        "tokens_per_step": tokens_per_step, "trace": trace,
        "device": device,
    }


def reference_phase(jax, family, optimizer, key, batches, micro_batch,
                    counter, device, *, rehearsal: bool) -> list:
    """The plain reference's losses over ``batches`` from the parameters the
    system started from, drawn again from ``key``. It runs once the window
    has closed, ``memory_peak_bytes`` is read and the system's state is
    released, so it has the device to itself and is no part of ``setup_s``;
    the ``reference`` line says what it held and what it left behind."""

    def in_use():
        stats = device.memory_stats() or {}
        return stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")

    t0 = time.perf_counter()
    counter.take()
    live_before = len(jax.live_arrays())
    bytes_before, peak_before = in_use()
    losses = reference.make_reference(
        family.reference_loss, optimizer, micro_batch=micro_batch
    )
    ref_losses = losses(family.init_params(key), batches)
    bytes_after, peak_after = in_use()
    emit(
        "reference", rehearsal=rehearsal, losses=ref_losses,
        seconds=time.perf_counter() - t0, cache=counter.take(),
        **losses.phase,
        # The runtime's high-water mark is the process's: it is the
        # phase's own where the phase passed what the system had reached.
        peak_bytes_in_use_before=peak_before, peak_bytes_in_use=peak_after,
        bytes_in_use_before=bytes_before, bytes_in_use_after=bytes_after,
        live_arrays_before=live_before,
        live_arrays_after=len(jax.live_arrays()),
    )
    return ref_losses


def traced_steps(jax, step, state, batches, built, untraced_gaps_ms,
                 trace_dump):
    """About ten steady steps under ``jax.profiler.trace``, reduced."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the loop's own spans are enough
    options.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with jax.profiler.trace(tmp, profiler_options=options):
            state, rec = loop.run_window(
                step, state, batches, iterations=TRACED_ITERATIONS,
                annotate=True,
            )
        paths = glob.glob(
            os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb")
        )
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb, found {paths}")
        events, inventory = trace_lib.read_xplane(paths[0])
        size = os.path.getsize(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    emit("trace_inventory", xplane_bytes=size, lines=inventory,
         kernel_names=built["pallas_call_names"],
         collective_names=built["collective_names"])
    if trace_dump:
        os.makedirs(os.path.dirname(trace_dump) or ".", exist_ok=True)
        trace_lib.save_events(events, trace_dump)
    summary = trace_lib.summarize(
        events, kernel_names=built["pallas_call_names"],
        collective_names=built["collective_names"],
    )
    traced_gaps = stats.gaps_ms(rec["stamps"])
    emit(
        "trace", xplane_bytes=size, events=len(events),
        window_s=summary.window_s, steps=summary.steps,
        devices=[
            {f.name: getattr(d, f.name) for f in dataclasses.fields(d)
             if f.name != "op_seconds"}  # thousands of names: readers only
            | {"idle_share": d.idle_share, "ops_named": len(d.op_seconds)}
            for d in summary.devices
        ],
        traced_step_ms_p50=stats.percentile(traced_gaps, 50),
        untraced_step_ms_p50=stats.percentile(untraced_gaps_ms, 50),
        tracing_overhead=(
            stats.percentile(traced_gaps, 50)
            / stats.percentile(untraced_gaps_ms, 50) - 1.0
        ),
    )
    return state, summary


def end_to_end(record: dict) -> dict:
    """The four end-to-end readings, by metric name."""
    return {
        "tokens_per_s_per_chip": record["rate"]["tokens_per_s_per_chip"],
        "step_ms_p90": stats.percentile(record["gaps_ms"], 90),
        "peak_hbm_gb": record["built"]["plan"]["peak_bytes"] / 1e9,
        "setup_s": record["setup_s"],
    }


def per_layer(record: dict, bench_dir: str) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    cell = record["cell"]
    out = {}
    for m in resolve.metrics_for(cell.manifest, "per_layer", cell.name):
        value = resolve.load_layer_metric(bench_dir, m["name"]).read(record)
        if value is not None:
            out[m["name"]] = value
    return out


def result_line(record: dict, bench_dir: str, traced: bool) -> dict:
    cell = record["cell"]
    group = "per_layer" if traced else "end_to_end"
    values = per_layer(record, bench_dir) if traced else end_to_end(record)
    units = {
        m["name"]: m["unit"]
        for m in resolve.metrics_for(cell.manifest, group, cell.name)
    }
    line = {
        "correct": record["correct"], "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": {
            name: {"value": values[name], "unit": unit}
            for name, unit in units.items() if name in values
        },
        "device": record["device"],
    }
    if traced and record["trace"] is not None:
        worst = max(record["trace"].devices, key=lambda d: d.idle_share)
        labels = record["built"]["labels"]
        line["breakdown"] = {
            # the trace's name, then where the compiled HLO says it is from
            "device_ops": [
                [f"{name} {labels.get(name, '')[-100:]}".strip(), seconds]
                for name, seconds in worst.top_ops
            ],
            "idle_gaps": worst.idle_gaps,
        }
    # last: each number ``correct`` was decided from, beside its limit
    line["compared"] = record["compared"]
    return line


def main(argv, *, root: str, bench_dir: str, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--trace-dump", default=None,
        help="with --trace 1: also save the parsed events (gzipped JSON), "
        "which is how the fixtures under benchmark/tests were recorded",
    )
    args = ap.parse_args(argv)
    try:
        cell = load_cell(root, bench_dir, args.workload)
        record = measure(
            cell, bench_dir=bench_dir, seed=args.seed, seconds=args.seconds,
            traced=bool(args.trace), t_start=t_start,
            trace_dump=args.trace_dump,
        )
    except Refused as e:
        # No result line: the reason goes to stderr, the exit code is 1.
        print(json.dumps({"correct": False, "reason": str(e)}),
              file=sys.stderr, flush=True)
        return 1
    if record["reasons"]:
        emit("incorrect", reasons=record["reasons"])
    print(json.dumps(result_line(record, bench_dir, bool(args.trace))),
          flush=True)
    # the same numbers as the last lines of standard error
    for name, (value, limit) in record["compared"].items():
        print(f"compared {name}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    return 0
