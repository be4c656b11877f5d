"""Headline benchmark: ResNet-50 synthetic training throughput + MFU.

TPU-native reproduction of the reference's synthetic benchmark
(``examples/tensorflow2/tensorflow2_synthetic_benchmark.py:25-44``): random
images, ResNet-50, SGD+momentum, data-parallel DistributedOptimizer,
report images/sec. Prints ONE JSON line.

Timing method: ``ITERS`` steps run inside ONE jitted ``lax.fori_loop``
whose carry is (params, batch_stats, opt_state), closed by a device→host
scalar fetch (the loop-carried dependence keeps XLA from hoisting the
body). That times the device program alone: no step is dispatched from
the host, so the framework's per-step host cost is NOT in these numbers
(ROADMAP S2).

Reported metrics:

* ``value`` — images/sec/chip (the north-star metric, BASELINE.md).
* ``step_time_ms`` — per-step wall time of the compiled training step.
* ``mfu`` — model FLOPs utilization: analytic training FLOPs
  (3x forward, ~12.33 GFLOP/image at 224x224) over the chip's nominal
  bf16 peak. Compiled-HLO FLOPs (``cost_analysis``) are also reported;
  they run ~2x analytic because XLA counts backward-conv algebra.
* ``vs_baseline`` — the reference publishes per-device throughput only
  for ResNet-101 on Pascal GPUs: 1656.82 img/s on 16 GPUs = 103.55
  img/s/device (``docs/benchmarks.rst:28-43``); that is the closest
  documented per-device number for the north-star comparison.

Every time, MFU and bandwidth share quoted in this file's docstrings is a
reading from before PR 1, on another installation; today's code is not
measured (``PERF.md``).

Where the time went then (full per-HLO device-trace analysis:
``docs/perf_analysis_resnet_r03.md``, captured with
``tools/profile_step.py``, since replaced by ``benchmark/split.py``): the 46.8 ms device step is 60% backward-conv
fusions, 18% forward-conv fusions — and XLA **already fuses the BN batch
stats and BN-backward reductions into those conv fusions**
(standalone forward BN-stats reduces: 0.35 ms/step). The dominant
fusions run at ~92% of the chip's HBM bandwidth roofline; total logical
traffic is ~44 GB/step, i.e. ~36 FLOP/byte against the v5e's ridge of
~241 FLOP/byte. ResNet-50/224/bs128 in bf16 is memory-bound by
construction on this chip: eliminating BN-stats work entirely
(eval-mode ablation) only reaches MFU 0.187, and batch-256,
space-to-depth-stem and Pallas-BN variants all measured no better (the
experiment table is in the doc). MFU ≈ 0.16 *is* the roofline for this
architecture/dtype, which is why the MFU showcase below is BERT
(matmul-dominated, ~0.51 MFU on the same chip after the r4 kernel and
fusion work — ``docs/perf_analysis_bert_r04.md``) — both lines are
emitted by default so the driver records them together.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

import horovod_tpu as hvd
from horovod_tpu.models import ResNet50
# The analytic flop/peak model lives in the obs plane so the live
# step-metrics MFU gauge (HVDTPU_METRICS=1) and these bench lines can
# never disagree; re-exported names keep older tooling imports working.
from horovod_tpu.obs.flops import (
    PEAK_TFLOPS_BF16,  # noqa: F401  (re-export)
    RESNET50_TRAIN_FLOPS_PER_IMAGE as ANALYTIC_FLOPS_PER_IMAGE,
    peak_tflops as _peak_tflops,
)
from horovod_tpu.utils.compile_cache import enable_compile_cache
from jax.sharding import PartitionSpec as P

BASELINE_IMG_PER_SEC_PER_DEVICE = 103.55

BATCH_PER_CHIP = 128
IMAGE_SIZE = 224
ITERS = 30


N_WINDOWS = 5


def _mem_plan_record(loss_fn, params, batch, remat=None, act_quant=None,
                     compute_dtype=None):
    """Predicted-vs-actual memory for one bench config: plan the exact
    ``dp.make_train_step`` build statically (``analysis/memory``), run
    ONE real step, and gate the prediction against what the host/device
    actually allocated — ``jax.live_arrays`` bytes on CPU (resident
    state), ``device.memory_stats()`` peak on TPU — so the planner's
    model drifts loudly in the bench record, never silently.

    NOTE: the step donates ``state``, so the caller's ``params`` arrays
    are CONSUMED — call this after every other use of them.
    """
    from horovod_tpu.analysis import memory as _mem
    from horovod_tpu.parallel import dp

    step, opt = dp.make_train_step(
        loss_fn, optax.adamw(1e-4), lint=False, remat=remat,
        act_quant=act_quant, compute_dtype=compute_dtype,
    )
    state = dp.init_state(params, opt)
    batch = jax.tree.map(jnp.asarray, batch)
    plan = step.memplan(state, batch)
    dev = jax.devices()[0]
    if dev.platform != "cpu" and getattr(dev, "memory_stats", None):
        measured, source = _mem.measure_step_bytes(
            lambda: step(state, batch)
        )
    else:
        # CPU host: live-bytes delta across the step (old state donated
        # away, new state + loss appear) plus the still-live batch =
        # the resident (state, batch) footprint the plan's outer avals
        # predict.
        before = _mem.snapshot_live_ids()
        out = step(state, batch)
        jax.block_until_ready(out)
        measured = _mem.live_array_bytes(exclude_ids=before) + sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(batch)
        )
        source = "live_arrays"
    return _mem.compare_to_measured(plan, measured, source)


def _timed_loop(run_iters, args0, drain_idx=3):
    """Warmup (compile+run), then time ``N_WINDOWS`` more calls on the
    ORIGINAL arrays — outputs carry mesh-tagged avals whose signature
    differs and feeding them back would retrace inside the timing window.

    Returns ``(median_seconds, spread_seconds)`` where spread is max−min
    across windows: a single window left the r4 overhead controls with an
    unexplained ±8% swing (VERDICT r4 #2); the median with a reported
    spread makes every overhead claim carry its own noise bar."""
    out = run_iters(*args0)
    val = float(out[drain_idx])
    if not np.isfinite(val):
        raise RuntimeError(f"non-finite loss in benchmark: {val}")
    times = []
    for _ in range(N_WINDOWS):
        t0 = time.perf_counter()
        out = run_iters(*args0)
        val = float(out[drain_idx])
        times.append(time.perf_counter() - t0)
        if not np.isfinite(val):
            raise RuntimeError(f"non-finite loss in benchmark: {val}")
    return float(np.median(times)), float(max(times) - min(times))


def _raw_jax_control(one_step_raw, init_carry, data_args, iters, drain_idx):
    """Same-chip no-framework control line (VERDICT r3 #2): the identical
    train step written in plain JAX — ``jax.jit``, bare optax, no
    ``hvd.spmd`` / ``DistributedOptimizer`` / collectives — timed with the
    same in-program fori_loop + host-fetch method.  The honest denominator
    for "the framework adds no overhead": on ONE chip the collectives are
    identity, so any step-time delta IS framework tax.  On n>1 chips the
    comparison is invalid (the framework step pays real ICI collectives
    the control does not), so callers emit null there."""

    @jax.jit
    def run_raw(*args):
        carry0, data = args[: len(init_carry)], args[len(init_carry):]

        def body(_, carry):
            return one_step_raw(carry, data)

        return lax.fori_loop(0, iters, body, carry0)

    args0 = tuple(init_carry) + tuple(data_args)
    return _timed_loop(run_raw, args0, drain_idx=drain_idx)


def _overhead_pct(step_ms, raw_ms):
    return round((step_ms - raw_ms) / raw_ms * 100, 2)


def _bert_setup(n):
    """BERT-base MLM benchmark setup — config, params, synthetic batch,
    and ``loss_fn(params, batch)``. ONE definition shared by
    :func:`bench_bert` and :func:`bench_overlap` so the overlap on/off
    pair times exactly the model the headline line reports.

    Canonical BERT pretraining shape (max_len 512). Measured on v5e:
    32x512 → ~43% MFU vs 128x128 → ~38% (longer sequences amortize the
    embedding/layernorm traffic against the matmuls); batch 64x512
    exceeds HBM even with flash attention (the 30522-vocab MLM logits
    dominate), and remat costs more than it buys here. r4 raised this
    step 135.9 → ~115 ms (MFU 0.435 → 0.51): variadic-psum fusion
    (no pack/unpack copies), bf16-native MXU matmuls + head-grouped
    grids in the flash kernels, and head-major attention layout — the
    full trace analysis is docs/perf_analysis_bert_r04.md."""
    from horovod_tpu.models.bert import BertConfig, BertModel

    batch, seq = 32, 512
    cfg = BertConfig.base()
    model = BertModel(cfg)
    tokens = jnp.zeros((n * batch, seq), jnp.int32)
    targets = jnp.zeros((n * batch, seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:2])["params"]

    def loss_fn(p, b):
        toks, tgts = b
        logits = model.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgts
        ).mean()

    return cfg, model, params, (tokens, targets), loss_fn, batch, seq


def _gpt2_setup(n, remat=None, batch=None):
    """GPT-2 small causal-LM benchmark setup, shared the same way as
    :func:`_bert_setup`. Measured on v5e: r4 kernels, bs16 -> 119.2k
    tok/s (MFU 0.517); bs32 OOM *without* remat. r11 defaults to bs32 +
    selective remat (`dots_saveable`: every matmul output stays
    resident — zero MXU recompute — and only the elementwise chains
    recompute, roughly halving live activation HBM), which is exactly
    the recompute-for-batch trade ISSUE 11 targets for MFU ≥ 0.60.
    `HVT_BENCH_GPT2_BATCH` / `HVT_BENCH_GPT2_REMAT` override (set
    `HVT_BENCH_GPT2_REMAT=none HVT_BENCH_GPT2_BATCH=16` for the r4
    configuration)."""
    import os as _os

    from horovod_tpu.ops.remat import checkpoint_fn as _remat_wrap

    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel

    if batch is None:
        batch = int(_os.environ.get("HVT_BENCH_GPT2_BATCH", "32"))
    if remat is None:
        remat = _os.environ.get("HVT_BENCH_GPT2_REMAT", "dots_saveable")
    seq = 1024
    cfg = GPT2Config.small()
    model = GPT2LMModel(cfg)
    tokens = jnp.zeros((n * batch, seq + 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:2, :seq])["params"]

    def loss_fn(p, b):
        (toks,) = b
        logits = model.apply({"params": p}, toks[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:]
        ).mean()

    loss_fn = _remat_wrap(loss_fn, remat)
    return cfg, model, params, (tokens,), loss_fn, batch, seq


def bench_bert():
    """Secondary benchmark: BERT-base MLM training (BASELINE.json config
    #3 names BERT-base as the second north-star model). Transformers are
    the shape TPUs are built for — this shows the framework's MFU ceiling
    isn't the conv-backward-bound ResNet number."""
    hvd.init()
    n = hvd.size()
    cfg, model, params, (tokens, targets), loss_fn, batch, seq = _bert_setup(n)
    iters = 30
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
    opt_state = opt.init(params)
    wa = hvd.WORLD_AXIS

    def one_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, (tokens, targets))
        )(params)
        updates, new_opt = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, hvd.allreduce(loss)

    @hvd.spmd(in_specs=(P(), P(), P(wa), P(wa)), out_specs=(P(), P(), P()))
    def run_iters(params, opt_state, tokens, targets):
        def body(_, carry):
            p, os_, _loss = carry
            return one_step(p, os_, tokens, targets)

        return lax.fori_loop(
            0, iters, body, (params, opt_state, jnp.zeros((), jnp.float32))
        )

    dt, dt_spread = _timed_loop(
        run_iters, (params, opt_state, tokens, targets), drain_idx=2
    )
    seqs_per_sec = iters * n * batch / dt / n
    step_ms = dt / iters * 1e3
    step_spread_ms = dt_spread / iters * 1e3

    # Raw-JAX control: same model/step, no framework (single-chip only —
    # with real collectives in the framework step the delta would conflate
    # ICI time with framework tax).
    raw_step_ms = None
    if n == 1:
        raw_opt = optax.adamw(1e-4)

        def one_step_raw(carry, data):
            p, os_, _loss = carry
            loss, grads = jax.value_and_grad(lambda q: loss_fn(q, data))(p)
            updates, new_os = raw_opt.update(grads, os_, p)
            return optax.apply_updates(p, updates), new_os, loss

        raw_dt, raw_spread = _raw_jax_control(
            one_step_raw,
            (params, raw_opt.init(params), jnp.zeros((), jnp.float32)),
            (tokens[:batch], targets[:batch]),
            iters,
            drain_idx=2,
        )
        raw_step_ms = raw_dt / iters * 1e3
        raw_spread_ms = raw_spread / iters * 1e3
    # 6*N convention counts matmul-participating params only: embedding
    # lookups (wte/wpe/type tables) perform no FLOPs. The untied
    # mlm_decoder IS a real matmul and stays in.
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    n_params = sum(
        int(np.prod(leaf.shape))
        for path, leaf in flat
        if not any(
            getattr(k, "key", None) in ("wte", "wpe", "wtt") for k in path
        )
    )
    # Transformer rule of thumb (obs.flops): 6*params FLOPs/token
    # fwd+bwd, plus 12*L*s*d attention term.
    flops_per_token = hvd.obs.flops.transformer_flops_per_token(
        n_params, cfg.n_layers, seq, cfg.d_model
    )
    achieved = seqs_per_sec * seq * flops_per_token / 1e12
    peak = _peak_tflops(jax.devices()[0])
    print(
        json.dumps(
            {
                "metric": "bert_base_mlm_sequences_per_sec_per_chip",
                "value": round(seqs_per_sec, 2),
                "unit": "sequences/sec/chip",
                "vs_baseline": None,
                "raw_jax_step_ms": (
                    round(raw_step_ms, 2) if raw_step_ms else None
                ),
                "raw_jax_step_ms_spread": (
                    round(raw_spread_ms, 2) if raw_step_ms else None
                ),
                "framework_overhead_pct": (
                    _overhead_pct(step_ms, raw_step_ms)
                    if raw_step_ms
                    else None
                ),
                "step_time_ms": round(step_ms, 2),
                "step_ms_spread": round(step_spread_ms, 2),
                "timing_windows": N_WINDOWS,
                "batch_per_chip": batch,
                "seq_len": seq,
                "mfu": round(achieved / peak, 4) if np.isfinite(peak) else None,
                "analytic_tflops_per_chip": round(achieved, 1),
                "peak_tflops_bf16": peak if np.isfinite(peak) else None,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,  # survives a driver timeout killing the next model's compile
    )


def bench_gpt2():
    """Third default line: GPT-2 small (124M) causal-LM training —
    BASELINE.json config #5's model on the chip itself (the Spark/elastic
    harness around it is exercised in
    ``examples/spark/spark_gpt2_elastic.py``)."""
    hvd.init()
    n = hvd.size()
    cfg, model, params, (tokens,), loss_fn, batch, seq = _gpt2_setup(n)
    iters = 20  # ~2.8 s per timed call (see bench_bert note)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
    opt_state = opt.init(params)
    wa = hvd.WORLD_AXIS

    def one_step(params, opt_state, toks):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, (toks,)))(
            params
        )
        updates, new_opt = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, hvd.allreduce(loss)

    @hvd.spmd(in_specs=(P(), P(), P(wa)), out_specs=(P(), P(), P()))
    def run_iters(params, opt_state, toks):
        def body(_, carry):
            p, os_, _loss = carry
            return one_step(p, os_, toks)

        return lax.fori_loop(
            0, iters, body, (params, opt_state, jnp.zeros((), jnp.float32))
        )

    dt, dt_spread = _timed_loop(
        run_iters, (params, opt_state, tokens), drain_idx=2
    )
    toks_per_sec = iters * batch * seq / dt  # per chip by construction
    step_ms = dt / iters * 1e3
    step_spread_ms = dt_spread / iters * 1e3

    raw_step_ms = None
    if n == 1:
        raw_opt = optax.adamw(1e-4)

        def one_step_raw(carry, data):
            p, os_, _loss = carry
            loss, grads = jax.value_and_grad(lambda q: loss_fn(q, data))(p)
            updates, new_os = raw_opt.update(grads, os_, p)
            return optax.apply_updates(p, updates), new_os, loss

        raw_dt, raw_spread = _raw_jax_control(
            one_step_raw,
            (params, raw_opt.init(params), jnp.zeros((), jnp.float32)),
            (tokens[:batch],),
            iters,
            drain_idx=2,
        )
        raw_step_ms = raw_dt / iters * 1e3
        raw_spread_ms = raw_spread / iters * 1e3
    # 6*N matmul-params + attention term (wte tied as the LM head DOES
    # matmul, so it stays in the count; wpe lookups do not).
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    n_params = sum(
        int(np.prod(leaf.shape))
        for path, leaf in flat
        if not any(getattr(k, "key", None) == "wpe" for k in path)
    )
    flops_per_token = hvd.obs.flops.transformer_flops_per_token(
        n_params, cfg.n_layers, seq, cfg.d_model
    )
    achieved = toks_per_sec * flops_per_token / 1e12
    peak = _peak_tflops(jax.devices()[0])
    # Last: the one-step memory gate donates (consumes) `params`.
    try:
        mem_plan = _mem_plan_record(loss_fn, params, (tokens,))
    except Exception as e:  # never let the memory gate kill the bench line
        mem_plan = {"ok": None, "error": f"{type(e).__name__}: {e}"}
    print(
        json.dumps(
            {
                "metric": "gpt2_small_tokens_per_sec_per_chip",
                "mem_plan": mem_plan,
                "value": round(toks_per_sec, 1),
                "unit": "tokens/sec/chip",
                "vs_baseline": None,
                "raw_jax_step_ms": (
                    round(raw_step_ms, 2) if raw_step_ms else None
                ),
                "raw_jax_step_ms_spread": (
                    round(raw_spread_ms, 2) if raw_step_ms else None
                ),
                "framework_overhead_pct": (
                    _overhead_pct(step_ms, raw_step_ms)
                    if raw_step_ms
                    else None
                ),
                "step_time_ms": round(step_ms, 2),
                "step_ms_spread": round(step_spread_ms, 2),
                "timing_windows": N_WINDOWS,
                "batch_per_chip": batch,
                "seq_len": seq,
                "mfu": round(achieved / peak, 4) if np.isfinite(peak) else None,
                "analytic_tflops_per_chip": round(achieved, 1),
                "peak_tflops_bf16": peak if np.isfinite(peak) else None,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,  # survives a driver timeout killing the next model's compile
    )


def bench_overlap(which="gpt2", accum_steps=4, iters=12):
    """Overlap pipeline on/off pair in ONE run (one JSON line).

    Times the SAME model/optimizer/microbatching twice through
    ``dp.make_train_step`` — ``overlap=False`` then ``overlap=True`` — so
    the delta isolates the overlap machinery (staggered per-bucket
    dispatch + latency-hiding-scheduler options), not the accumulation.
    Unlike the headline lines, steps are dispatched from a Python loop
    over a ``prefetch_to_device`` iterator (blocked only at the end):
    the async-dispatch pipeline the overlap work targets is exactly what
    is measured. ``overlap_efficiency`` is the exposed-vs-total comm
    accounting from :mod:`horovod_tpu.obs.overlap` (null on chips with
    no ICI model, e.g. the CPU smoke mesh).
    """
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.obs import overlap as obs_overlap
    from horovod_tpu.parallel import dp

    ctx = hvd.init()
    n = hvd.size()
    # ONE definition per model (_bench_setup_for): the on/off pair must
    # time what the headline lines report; mlp is the CPU-smoke scale
    # that validates the overlap plumbing end to end on the virtual
    # mesh in seconds (no efficiency claim there — the ring model
    # reports null off-TPU).
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(which, n)

    sharding = NamedSharding(ctx.mesh, P(hvd.WORLD_AXIS))

    def run(overlap):
        step, opt = dp.make_train_step(
            loss_fn, optax.adamw(1e-4), overlap=overlap,
            accum_steps=accum_steps,
        )
        state = dp.init_state(jax.tree.map(jnp.array, params), opt)

        def repeat():
            while True:
                yield batch_np

        it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
        state, loss = step(state, next(it))  # compile + warmup
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step(state, next(it))
        jax.block_until_ready((state, loss))
        return (time.perf_counter() - t0) / iters * 1e3

    off_ms = run(False)
    on_ms = run(True)
    wire_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )
    pair = obs_overlap.record_overlap_pair(
        on_ms, off_ms, wire_bytes=wire_bytes, n_chips=n,
        device=jax.devices()[0],
    )
    print(
        json.dumps(
            {
                "metric": "comm_overlap_onoff",
                "model": which,
                "accum_steps": accum_steps,
                "batch_per_chip": batch,
                "seq_len": seq,
                "gradient_wire_bytes": wire_bytes,
                "prefetch_depth": 2,
                "timing_iters": iters,
                **{
                    k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in pair.items()
                },
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )


def bench_quant(which="gpt2", quant="int8", accum_steps=1, overlap=False,
                iters=12):
    """Quantized-collective on/off pair in ONE run (one JSON line),
    mirroring ``comm_overlap_onoff``.

    Times the SAME model/optimizer twice through ``dp.make_train_step``
    — ``compression=Compression.none`` then the quantized wire — so the
    delta isolates the wire format (quant/dequant compute vs collective
    bytes saved). Composes with ``--overlap --accum-steps K`` (both runs
    get the same pipeline shape). On a single chip the collectives are
    local so ``speedup`` mostly prices the quant/dequant overhead; the
    wire-byte reduction itself is audited analytically
    (``tools/comm_audit.py --quant``) and the JSON carries both numbers.
    """
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.ops.compression import Compression
    from horovod_tpu.ops.quantization import quant_spec, quantized_wire_bytes
    from horovod_tpu.parallel import dp
    from horovod_tpu.utils import env as _hvd_env

    ctx = hvd.init()
    n = hvd.size()
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(which, n)

    sharding = NamedSharding(ctx.mesh, P(hvd.WORLD_AXIS))

    def run(compression):
        step, opt = dp.make_train_step(
            loss_fn, optax.adamw(1e-4), compression=compression,
            overlap=overlap, accum_steps=accum_steps,
        )
        state = dp.init_state(jax.tree.map(jnp.array, params), opt)

        def repeat():
            while True:
                yield batch_np

        it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
        state, loss = step(state, next(it))  # compile + warmup
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step(state, next(it))
        jax.block_until_ready((state, loss))
        if not np.isfinite(float(loss)):
            raise RuntimeError(f"non-finite loss in quant bench: {loss}")
        return (time.perf_counter() - t0) / iters * 1e3

    off_ms = run(Compression.none)
    on_ms = run(Compression.by_name(quant))
    grad_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )
    n_elems = sum(leaf.size for leaf in jax.tree.leaves(params))
    block = _hvd_env.quant_block()
    q_bytes = quantized_wire_bytes(n_elems, block, quant_spec(quant))
    print(
        json.dumps(
            {
                "metric": "quant_onoff",
                "model": which,
                "quant": quant,
                "block": block,
                "accum_steps": accum_steps,
                "overlap": bool(overlap),
                "batch_per_chip": batch,
                "seq_len": seq,
                "timing_iters": iters,
                "step_ms_off": round(off_ms, 3),
                "step_ms_on": round(on_ms, 3),
                "speedup": round(off_ms / on_ms, 4) if on_ms else None,
                "gradient_wire_bytes_off": grad_bytes,
                "gradient_wire_bytes_on": q_bytes,
                "wire_reduction_vs_grad_dtype": round(
                    q_bytes / grad_bytes, 4
                ),
                "error_feedback": True,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )


def bench_fp8(iters=12):
    """fp8 training-matmul on/off pair in ONE run (one JSON line),
    mirroring ``quant_onoff`` — but for the COMPUTE dtype, not the wire.

    Unlike the wire pair the two sides are different builds:
    ``compute_dtype='fp8'`` rebuilds the model config (``Fp8DotGeneral``
    injected into every Dense/attention matmul) and the param tree grows
    the ``fp8_*`` delayed-scaling state, so each side inits its own
    params and the speedup prices cast+scale overhead vs MXU fp8
    throughput (on CPU both sides run the jax twin: parity smoke, no
    perf claim). The convergence check trains both sides on the same
    fixed batch and requires the fp8 loss to stay finite, decrease, and
    land within ``HVT_BENCH_FP8_LOSS_RTOL`` (default 0.15) of the
    higher-precision final loss — the same "quantization must not eat
    the optimization signal" gate ``quant_onoff`` applies to the wire.
    ``HVT_BENCH_FP8_SIZE=small`` runs the GPT-2-small shapes (TPU);
    the default tiny config keeps the pair CPU-smoke-runnable.
    """
    import os as _os

    from jax.sharding import NamedSharding

    from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel
    from horovod_tpu.ops.fp8 import fp8_state_gauges
    from horovod_tpu.parallel import dp

    ctx = hvd.init()
    n = hvd.size()
    size = _os.environ.get("HVT_BENCH_FP8_SIZE", "tiny")
    batch = int(
        _os.environ.get("HVT_BENCH_FP8_BATCH", "8" if size == "tiny" else "16")
    )
    rtol = float(_os.environ.get("HVT_BENCH_FP8_LOSS_RTOL", "0.15"))
    sharding = NamedSharding(ctx.mesh, P(hvd.WORLD_AXIS))

    def build(compute_dtype):
        mk = GPT2Config.tiny if size == "tiny" else GPT2Config.small
        cfg = mk(compute_dtype=compute_dtype)
        model = GPT2LMModel(cfg)
        seq = min(cfg.max_len, 1024 if size == "small" else 128)
        rng = np.random.RandomState(0)
        tokens = rng.randint(
            0, cfg.vocab_size, size=(n * batch, seq + 1)
        ).astype(np.int32)
        params = model.init(
            jax.random.PRNGKey(0), jnp.asarray(tokens[:2, :seq])
        )["params"]

        def loss_fn(p, b):
            (toks,) = b
            logits = model.apply({"params": p}, toks[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, toks[:, 1:]
            ).mean()

        return params, (tokens,), loss_fn, seq

    def run(compute_dtype):
        params, batch_np, loss_fn, seq = build(compute_dtype)
        step, opt = dp.make_train_step(
            loss_fn, optax.adamw(1e-3), compute_dtype=compute_dtype
        )
        state = dp.init_state(jax.tree.map(jnp.array, params), opt)

        def repeat():
            while True:
                yield batch_np

        it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
        state, loss = step(state, next(it))  # compile + warmup
        jax.block_until_ready(loss)
        first = float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step(state, next(it))
        jax.block_until_ready((state, loss))
        ms = (time.perf_counter() - t0) / iters * 1e3
        last = float(loss)
        if not np.isfinite(first):
            raise RuntimeError(
                f"non-finite warmup loss in fp8 bench "
                f"(compute_dtype={compute_dtype!r}): {first}"
            )
        gauges = (
            {k: round(v, 6) for k, v in fp8_state_gauges(state.params).items()}
            if compute_dtype == "fp8"
            else {}
        )
        return ms, first, last, seq, gauges

    off_ms, off_first, off_last, seq, _ = run("")
    on_ms, on_first, on_last, _, gauges = run("fp8")
    converged = bool(
        np.isfinite(on_last)
        and on_last < on_first
        and abs(on_last - off_last) <= rtol * max(abs(off_last), 1e-9)
    )
    print(
        json.dumps(
            {
                "metric": "fp8_onoff",
                "model": "gpt2",
                "size": size,
                "compute_dtype": "fp8",
                "batch_per_chip": batch,
                "seq_len": seq,
                "timing_iters": iters,
                "step_ms_off": round(off_ms, 3),
                "step_ms_on": round(on_ms, 3),
                "speedup": round(off_ms / on_ms, 4) if on_ms else None,
                "loss_off_first": round(off_first, 5),
                "loss_off": round(off_last, 5),
                "loss_on_first": round(on_first, 5),
                "loss_on": round(on_last, 5),
                "loss_rtol": rtol,
                "converged": converged,
                **gauges,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )
    if not converged:
        raise RuntimeError(
            "fp8 bench did not converge: "
            f"loss_on {on_first:.4f}->{on_last:.4f} vs loss_off "
            f"{off_last:.4f} (rtol {rtol})"
        )


def bench_act_quant(iters=12):
    """int8 activation-storage on/off pair in ONE run (one JSON line).

    The model is an activation-dominated MLP tower
    (``HVT_BENCH_ACTQ_WIDTH``/``_DEPTH``/``_BATCH`` override the
    default 8×512 at 2048 rows per chip) — deliberately NOT the tiny
    transformer zoo configs, whose planner peak sits in the ZeRO-1
    update phase where activation storage legitimately cannot move it.
    Alongside the timing pair the line carries the planner's predicted
    peak for both sides (the saving the int8 residuals buy) and the
    predicted-vs-measured gate (``analysis.memory.compare_to_measured``
    under ``HVDTPU_MEMPLAN_TOLERANCE``): device peak on TPU/GPU; on CPU
    hosts the measurable quantity is post-step resident bytes
    (``jax.live_arrays``), which gates the plan's ``global_state_bytes``
    — act-quant only moves the transient peak, so the resident check
    pins the accounting, not the saving.
    """
    import os as _os

    from horovod_tpu.models.mlp import MLP
    from horovod_tpu.utils import env as _hvd_env

    ctx = hvd.init()
    n = hvd.size()
    width = int(_os.environ.get("HVT_BENCH_ACTQ_WIDTH", "512"))
    depth = int(_os.environ.get("HVT_BENCH_ACTQ_DEPTH", "8"))
    batch = int(_os.environ.get("HVT_BENCH_ACTQ_BATCH", "2048"))

    model = MLP(features=(width,) * depth, num_classes=10)
    rng = np.random.RandomState(0)
    x = rng.randn(n * batch, width).astype(np.float32)
    y = rng.randint(0, 10, size=(n * batch,)).astype(np.int32)
    batch_np = (x, y)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(x[:2]))["params"]

    def loss_fn(p, b):
        xs, ys = b
        logits = model.apply({"params": p}, xs)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, ys
        ).mean()

    off_ms, on_ms = _timed_step_pair(
        loss_fn, params, batch_np, ctx.mesh, iters,
        dict(optimizer=optax.adamw(1e-4), act_quant=""),
        dict(optimizer=optax.adamw(1e-4), act_quant="int8"),
    )
    # Planner prediction + drift gate per side (one extra real step each;
    # _mem_plan_record donates its params, so hand it fresh copies).
    rec_off = _mem_plan_record(
        loss_fn, jax.tree.map(jnp.array, params), batch_np, act_quant=""
    )
    rec_on = _mem_plan_record(
        loss_fn, jax.tree.map(jnp.array, params), batch_np, act_quant="int8"
    )
    peak_off = rec_off["predicted_peak_bytes"]
    peak_on = rec_on["predicted_peak_bytes"]
    print(
        json.dumps(
            {
                "metric": "act_quant_onoff",
                "model": "mlp",
                "act_quant": "int8",
                "width": width,
                "depth": depth,
                "batch_per_chip": batch,
                "timing_iters": iters,
                "step_ms_off": round(off_ms, 3),
                "step_ms_on": round(on_ms, 3),
                "overhead_pct": round((on_ms / off_ms - 1.0) * 100.0, 3)
                if off_ms
                else None,
                "peak_predicted_off": peak_off,
                "peak_predicted_on": peak_on,
                "predicted_peak_saving_pct": round(
                    (1.0 - peak_on / peak_off) * 100.0, 2
                )
                if peak_off
                else None,
                "peak_measured": rec_on["measured_bytes"],
                "measured_source": rec_on["source"],
                "memplan_ok": rec_on["ok"],
                "memplan_tolerance": _hvd_env.memplan_tolerance(),
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )
    if peak_on >= peak_off:
        raise RuntimeError(
            "act-quant bench: int8 activation storage did not reduce the "
            f"planned peak ({peak_on} >= {peak_off}) — the bench model is "
            "supposed to be activation-dominated; widen it or fix the plan"
        )


def _bench_setup_for(which, n, gpt2_remat=None, gpt2_batch=None):
    """Shared model pick for the on/off pair benches (gpt2 default; mlp
    is the CPU-smoke config). ``gpt2_remat``/``gpt2_batch`` override the
    gpt2 setup's baked-in remat and batch (the remat on/off pair needs a
    remat-free loss at a batch whose remat-OFF side still fits HBM)."""
    if which == "bert":
        _, _, params, device_batch, loss_fn, batch, seq = _bert_setup(n)
        return params, tuple(np.asarray(a) for a in device_batch), loss_fn, batch, seq
    if which == "mlp":
        rng = np.random.RandomState(0)
        batch, seq = 64, 0
        params = {
            "w1": jnp.asarray(rng.randn(64, 128) * 0.1, jnp.float32),
            "b1": jnp.zeros((128,), jnp.float32),
            "w2": jnp.asarray(rng.randn(128, 10) * 0.1, jnp.float32),
            "b2": jnp.zeros((10,), jnp.float32),
        }
        batch_np = (
            rng.randn(n * batch, 64).astype(np.float32),
            rng.randint(0, 10, size=(n * batch,)).astype(np.int32),
        )

        def loss_fn(p, b):
            x, y = b
            h = jax.nn.relu(x @ p["w1"] + p["b1"])
            logits = h @ p["w2"] + p["b2"]
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        return params, batch_np, loss_fn, batch, seq
    _, _, params, device_batch, loss_fn, batch, seq = _gpt2_setup(
        n, remat=gpt2_remat, batch=gpt2_batch
    )
    return params, tuple(np.asarray(a) for a in device_batch), loss_fn, batch, seq


def _timed_step_pair(loss_fn, params, batch_np, mesh, iters, make_kwargs_off,
                     make_kwargs_on):
    """Build the SAME model/step twice through ``dp.make_train_step``
    (kwargs off, then on) and time each with the prefetch-iterator loop
    the other on/off benches use. Returns ``(off_ms, on_ms)``."""
    from jax.sharding import NamedSharding

    from horovod_tpu.parallel import dp

    sharding = NamedSharding(mesh, P(hvd.WORLD_AXIS))

    def run(kwargs):
        step, opt = dp.make_train_step(loss_fn, **kwargs)
        state = dp.init_state(jax.tree.map(jnp.array, params), opt)

        def repeat():
            while True:
                yield batch_np

        it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
        state, loss = step(state, next(it))  # compile + warmup
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step(state, next(it))
        jax.block_until_ready((state, loss))
        if not np.isfinite(float(loss)):
            raise RuntimeError(f"non-finite loss in bench: {loss}")
        return (time.perf_counter() - t0) / iters * 1e3

    return run(make_kwargs_off), run(make_kwargs_on)


def bench_fused_update(which="gpt2", iters=12):
    """Fused optimizer-update on/off pair in ONE run (one JSON line),
    mirroring ``quant_onoff``.

    Times the SAME model through the ZeRO-1 sharded step twice —
    ``fused_update=False`` then ``True`` — with the identical
    ``fused_adamw`` inner optimizer, so the delta isolates the fused
    Pallas pass vs the unfused optax chain over the flat shards. On CPU
    both sides run the jax twin (parity smoke, no perf claim).
    """
    from horovod_tpu.optimizer import fused_adamw

    ctx = hvd.init()
    n = hvd.size()
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(which, n)
    shard_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    ) // n
    off_ms, on_ms = _timed_step_pair(
        loss_fn, params, batch_np, ctx.mesh, iters,
        dict(optimizer=fused_adamw(1e-4), sharded=True, fused_update=False),
        dict(optimizer=fused_adamw(1e-4), sharded=True, fused_update=True),
    )
    print(
        json.dumps(
            {
                "metric": "fused_update_onoff",
                "model": which,
                "batch_per_chip": batch,
                "seq_len": seq,
                "timing_iters": iters,
                "step_ms_off": round(off_ms, 3),
                "step_ms_on": round(on_ms, 3),
                "speedup": round(off_ms / on_ms, 4) if on_ms else None,
                "param_shard_bytes": shard_bytes,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )


def bench_remat(which="gpt2", policy="dots_saveable", iters=12):
    """Selective-remat on/off pair in ONE run (one JSON line).

    Times the SAME model/optimizer twice — ``remat='none'`` then the
    given policy — so the delta prices the recompute the policy trades
    for activation memory (the headroom that converts into batch on the
    HBM-bound transformer shapes; the bigger-batch configs themselves
    ride `HVT_BENCH_GPT2_BATCH`).
    """
    import os as _os

    ctx = hvd.init()
    n = hvd.size()
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(
        which, n, gpt2_remat="none",
        gpt2_batch=int(_os.environ.get("HVT_BENCH_GPT2_BATCH", "16")),
    )
    off_ms, on_ms = _timed_step_pair(
        loss_fn, params, batch_np, ctx.mesh, iters,
        dict(optimizer=optax.adamw(1e-4), remat="none"),
        dict(optimizer=optax.adamw(1e-4), remat=policy),
    )
    print(
        json.dumps(
            {
                "metric": "remat_onoff",
                "model": which,
                "policy": policy,
                "batch_per_chip": batch,
                "seq_len": seq,
                "timing_iters": iters,
                "step_ms_off": round(off_ms, 3),
                "step_ms_on": round(on_ms, 3),
                "recompute_overhead_pct": round(
                    (on_ms / off_ms - 1.0) * 100.0, 3
                ) if off_ms else None,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )


def bench_guard(which="gpt2", iters=12):
    """Gradient-guard on/off pair in ONE run (one JSON line), mirroring
    ``comm_overlap_onoff``/``quant_onoff``.

    Times the SAME model/optimizer twice through ``dp.make_train_step``
    — ``guard=False`` then ``guard=True`` — so the delta isolates the
    fail-silent defense's cost: the fused isfinite/sumsq screen, the
    two replica-uniform scalar psums, and the ``lax.cond`` commit. The
    budget is < 1% step time (``overhead_pct`` in the JSON); the screen
    reads memory the reduction touches anyway, so the cost is two tiny
    collectives and a select. The budget is a TPU claim: XLA:TPU
    forwards the untaken cond branch's buffers in place, while the CPU
    smoke mesh materializes them — a fixed few-ms absolute cost that
    dominates the tiny mlp's step but vanishes into a real model's.
    """
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.guard import GuardConfig
    from horovod_tpu.parallel import dp
    from horovod_tpu.utils import env as _hvd_env

    ctx = hvd.init()
    n = hvd.size()
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(which, n)

    sharding = NamedSharding(ctx.mesh, P(hvd.WORLD_AXIS))
    cfg = GuardConfig.from_env()

    def run(guard):
        step, opt = dp.make_train_step(
            loss_fn, optax.adamw(1e-4), guard=cfg if guard else False,
        )
        state = dp.init_state(
            jax.tree.map(jnp.array, params), opt, guard=guard
        )

        def repeat():
            while True:
                yield batch_np

        it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
        state, loss = step(state, next(it))  # compile + warmup
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step(state, next(it))
        jax.block_until_ready((state, loss))
        if not np.isfinite(float(loss)):
            raise RuntimeError(f"non-finite loss in guard bench: {loss}")
        if guard and int(state.guard.skipped):
            raise RuntimeError(
                "guard skipped clean steps in the bench — a false "
                "positive would poison the timing AND training"
            )
        return (time.perf_counter() - t0) / iters * 1e3

    off_ms = run(False)
    on_ms = run(True)
    print(
        json.dumps(
            {
                "metric": "guard_onoff",
                "model": which,
                "batch_per_chip": batch,
                "seq_len": seq,
                "timing_iters": iters,
                "step_ms_off": round(off_ms, 3),
                "step_ms_on": round(on_ms, 3),
                "overhead_pct": round((on_ms / off_ms - 1.0) * 100.0, 3)
                if off_ms
                else None,
                "spike_sigma": cfg.spike_sigma,
                "max_skips": cfg.max_skips,
                "warmup": cfg.warmup,
                "audit_every": _hvd_env.guard_audit_every(),
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )


def bench_trace(which="gpt2", iters=12, out_dir=None):
    """Tracing-plane on/off pair in ONE run (one JSON line), mirroring
    ``guard_onoff``/``quant_onoff``.

    Times the SAME compiled step twice — ``HVDTPU_TRACE`` off, then the
    span recorder armed (`obs.trace.enable`) — so the delta prices the
    whole tracing plane: the per-call enabled check, the wall-clock
    reads, three ring appends per step and the ``block_until_ready``
    bracket. The budget is < 2% step time on the CPU smoke (enforced —
    a tracing plane you can't leave on in production is a debugging
    tool, not an observability plane); on TPU the bracket serializes
    host and device, so the pair is a ceiling there, not a production
    cost.
    """
    import tempfile

    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu.obs import trace as _tr
    from horovod_tpu.parallel import dp

    ctx = hvd.init()
    n = hvd.size()
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(which, n)
    sharding = NamedSharding(ctx.mesh, P(hvd.WORLD_AXIS))
    step, opt = dp.make_train_step(loss_fn, optax.adamw(1e-4))
    state = dp.init_state(jax.tree.map(jnp.array, params), opt)

    def repeat():
        while True:
            yield batch_np

    it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
    state, loss = step(state, next(it))  # compile + warmup
    jax.block_until_ready(loss)

    def window():
        nonlocal state
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(iters):
                state, loss = step(state, next(it))
            jax.block_until_ready((state, loss))
            times.append((time.perf_counter() - t0) / iters * 1e3)
        if not np.isfinite(float(loss)):
            raise RuntimeError(f"non-finite loss in trace bench: {loss}")
        # Min, not median: both modes' noise is one-sided (scheduler
        # preemptions only ever add), and the budget claim is about the
        # plane's intrinsic cost, not the host's worst jitter.
        return float(min(times))

    _tr.disable()
    off_ms = window()
    rec = _tr.enable(
        directory=out_dir or tempfile.mkdtemp(prefix="hvdtpu_trace_bench_")
    )
    on_ms = window()
    events = len(rec._ring)
    _tr.disable()
    overhead = round((on_ms / off_ms - 1.0) * 100.0, 3) if off_ms else None
    print(
        json.dumps(
            {
                "metric": "trace_onoff",
                "model": which,
                "batch_per_chip": batch,
                "seq_len": seq,
                "timing_iters": iters,
                "step_ms_off": round(off_ms, 3),
                "step_ms_on": round(on_ms, 3),
                "overhead_pct": overhead,
                "events_recorded": events,
                "ring_capacity": rec.capacity,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )
    if (
        jax.devices()[0].platform == "cpu"
        and overhead is not None
        and off_ms >= 5.0
        and overhead > 2.0
    ):
        # Gated only where 2% is resolvable: on a sub-5ms step (the
        # mlp smoke) scheduler jitter alone swings ±10% and the gate
        # would flake; the gpt2 CPU smoke's multi-second steps measure
        # the plane's per-step cost with µs of it in the noise floor.
        raise RuntimeError(
            f"tracing overhead {overhead}% exceeds the 2% CPU-smoke "
            "budget — the span plane regressed"
        )


def bench_goodput(which="gpt2", iters=12, out_dir=None):
    """Goodput-ledger accounting of a short instrumented run — ONE
    ``goodput`` JSON line (per-category seconds, the goodput fraction,
    and the conservation residual).

    Runs the instrumented train step through the prefetch pipeline with
    ``HVDTPU_GOODPUT`` armed, plus one blocking checkpoint save so the
    line exercises a non-compute category deterministically. The
    ``conservation_residual_s`` field is the live form of the ledger's
    unit invariant (sum of categories minus elapsed) — a nonzero value
    here is an instrumentation bug, not a slow host.
    """
    import tempfile

    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu import checkpoint as _ckpt
    from horovod_tpu.obs import goodput as _gp
    from horovod_tpu.parallel import dp

    ctx = hvd.init()
    n = hvd.size()
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(which, n)
    sharding = NamedSharding(ctx.mesh, P(hvd.WORLD_AXIS))
    step, opt = dp.make_train_step(loss_fn, optax.adamw(1e-4))
    state = dp.init_state(jax.tree.map(jnp.array, params), opt)

    def repeat():
        while True:
            yield batch_np

    _gp._reset_for_tests()
    _gp.enable()
    it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
    state, loss = step(state, next(it))  # compile + warmup
    jax.block_until_ready(loss)
    for _ in range(iters):
        state, loss = step(state, next(it))
    jax.block_until_ready((state, loss))
    if not np.isfinite(float(loss)):
        raise RuntimeError(f"non-finite loss in goodput bench: {loss}")
    _ckpt.save_checkpoint(
        out_dir or tempfile.mkdtemp(prefix="hvdtpu_goodput_bench_"),
        state, step=iters, force=True,
    )
    snap = _gp.ledger().snapshot()
    residual = sum(snap["totals"].values()) - snap["elapsed_s"]
    print(
        json.dumps(
            {
                "metric": "goodput",
                "model": which,
                "batch_per_chip": batch,
                "seq_len": seq,
                "timing_iters": iters,
                "fraction": round(snap["fraction"], 4),
                "elapsed_s": round(snap["elapsed_s"], 3),
                "categories_s": {
                    c: round(s, 3)
                    for c, s in snap["totals"].items()
                    if s > 0
                },
                "conservation_residual_s": round(residual, 6),
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )
    _gp._reset_for_tests()
    if abs(residual) > 1e-3:
        raise RuntimeError(
            f"goodput conservation violated by {residual:.6f}s — the "
            "ledger's sweep attribution regressed"
        )


def _pct(xs, q):
    """Index-percentile over a SORTED list; None when empty (e.g. TPOT
    of one-token streams — there are no inter-token deltas)."""
    if not xs:
        return None
    return xs[min(len(xs) - 1, max(0, int(q * len(xs)) - 1))]


def bench_serve(batch_size=8, workers=2, clients=16, requests=512,
                hidden=256, int8_pair=True, autotune=False):
    """Synthetic closed-loop load against the in-process serving pool —
    ONE ``serve_latency`` JSON line (throughput + p50/p95/p99).

    ``clients`` threads each submit-and-wait in a loop (closed-loop: a
    client's next request leaves only when its previous answer lands),
    so the offered concurrency is exactly ``clients`` and the dispatcher
    must continuous-batch to fill the fixed ``batch_size`` device shape.
    Latency is measured client-side (submit→result), end to end through
    queueing, batching, the jit step and response routing.

    ``int8_pair`` reruns the identical load with
    ``ServePool(weight_dtype='int8')`` — the in-kernel-scaled int8
    matmul path — and nests its numbers under ``"int8"`` in the same
    line, so the weight-dtype win stays machine-diffable next to the
    float baseline (``infer`` routes matmuls through ``qmatmul``; the
    float pool lowers that to plain ``x @ w``).
    """
    import threading

    from horovod_tpu.ops.quantization import qmatmul
    from horovod_tpu.serve import ServePool

    rng = np.random.RandomState(0)
    d_in, d_out = 64, 10
    params = {
        "w1": jnp.asarray(rng.randn(d_in, hidden) * 0.1, jnp.float32),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jnp.asarray(rng.randn(hidden, d_out) * 0.1, jnp.float32),
        "b2": jnp.zeros((d_out,), jnp.float32),
    }

    def infer(p, x):
        h = jax.nn.relu(qmatmul(x, p["w1"]) + p["b1"])
        return qmatmul(h, p["w2"]) + p["b2"]

    def run_load(weight_dtype):
        tune_cfg = False
        if autotune:
            # The serve twin of the closed-loop autotuner: tune the
            # batch fill window / watermarks against p95 under THIS
            # closed-loop load (small windows — the load is finite).
            from horovod_tpu import tune as _tune

            tune_cfg = _tune.AutotuneConfig(
                window_steps=4, warmup_steps=1, max_trials=6, patience=3
            )
        pool = ServePool(
            infer, params, workers=workers, batch_size=batch_size,
            batch_timeout_ms=1.0, request_timeout_secs=30.0,
            weight_dtype=weight_dtype, autotune=tune_cfg,
        ).start()
        example = jnp.asarray(rng.randn(d_in), jnp.float32)
        jax.block_until_ready(pool.submit(example).result(timeout=30.0))

        per_client = max(1, requests // clients)
        latencies = []
        lat_lock = threading.Lock()

        def client(k):
            x = jnp.asarray(rng.randn(d_in), jnp.float32)
            mine = []
            for _ in range(per_client):
                t = time.perf_counter()
                pool.submit(x).result(timeout=60.0)
                mine.append((time.perf_counter() - t) * 1e3)
            with lat_lock:
                latencies.extend(mine)

        threads = [
            threading.Thread(target=client, args=(k,)) for k in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        tuned = None
        if pool.tuner is not None:
            tuned = {
                "converged": pool.tuner.done,
                "trials": pool.tuner.search.n_trials,
                "vector": pool.tuner.applied,
                "best_p95_ms": (
                    round(-pool.tuner.search.best_score, 3)
                    if pool.tuner.search.n_trials else None
                ),
            }
        pool.stop()

        latencies.sort()

        out = {
            "requests": len(latencies),
            "throughput_rps": round(len(latencies) / wall, 1),
            "p50_ms": round(_pct(latencies, 0.50), 3),
            "p95_ms": round(_pct(latencies, 0.95), 3),
            "p99_ms": round(_pct(latencies, 0.99), 3),
            "dispatcher": pool.dispatcher,
        }
        if tuned is not None:
            out["autotune"] = tuned
        return out

    base = run_load("")
    disp = base.pop("dispatcher")
    line = {
        "metric": "serve_latency",
        "model": "mlp",
        "batch_size": batch_size,
        "workers": workers,
        "clients": clients,
        **base,
        "mean_batch_fill": round(
            disp.fill_sum / disp.n_batches, 4
        ) if disp.n_batches else None,
        "batches": disp.n_batches,
        "requeued": disp.n_requeued,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
    }
    if int8_pair:
        q = run_load("int8")
        q.pop("dispatcher")
        q["speedup_vs_float"] = (
            round(q["throughput_rps"] / base["throughput_rps"], 4)
            if base["throughput_rps"]
            else None
        )
        line["int8"] = q
    print(json.dumps(line), flush=True)


def bench_decode(streams=32, max_new=32, rows=4, workers=1, spec_k=3,
                 spec_pair=True):
    """Closed-loop streaming load against the token-level decode engine
    — ONE ``serve_decode`` JSON line (tokens/s/chip, TTFT and
    per-output-token percentiles, mean decode-batch fill, and the
    speculative on/off pair).

    Clients submit-and-stream in a loop (closed-loop: the next prompt
    leaves only when the previous stream resolves), so the engine must
    continuous-batch at DECODE granularity to keep its fixed rows full.
    TTFT is submit→first-token per stream; TPOT percentiles come from
    the true per-token commit timestamps. ``spec_pair`` reruns the same
    load with a ``spec_k``-proposal draft tier (the target's weights
    lightly perturbed — the high-accept regime) and nests its numbers
    under ``"speculative"``; greedy speculative decoding is output-
    invariant, so the pair times the SAME token streams.
    """
    import threading

    from horovod_tpu.serve import (
        CacheLM, CacheLMConfig, DecodeEngine, perturbed_params,
    )

    cfg = CacheLMConfig(
        vocab=128, n_layers=2, n_heads=4, head_dim=16, max_positions=512
    )
    model = CacheLM(cfg, block_size=16)
    params = model.init_params(0)
    draft = perturbed_params(params, 0.02)
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(1, cfg.vocab, size=rng.randint(4, 17)).tolist()
        for _ in range(streams)
    ]

    def run_load(spec):
        from horovod_tpu.obs import goodput as _gp

        gp_was = _gp.enabled()
        _gp.enable()
        gp_before = _gp.ledger().totals()
        eng = DecodeEngine(
            model, params, workers=workers, rows=rows,
            kv_blocks=16 * rows * workers, kv_block_size=16,
            max_seq_len=64, spec_k=spec_k if spec else 0,
            draft_params=draft if spec else None,
        ).start()
        # Warm the three compiled shapes (prefill/decode/verify) off
        # the clock.
        eng.submit(prompts[0], max_new).result(timeout=120.0)

        clients = rows * 2
        futs_done = []
        done_lock = threading.Lock()

        def client(k):
            mine = []
            for i in range(k, streams, clients):
                f = eng.submit(prompts[i], max_new)
                f.result(timeout=120.0)
                mine.append(f)
            with done_lock:
                futs_done.extend(mine)

        threads = [
            threading.Thread(target=client, args=(k,))
            for k in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        ttft = sorted(
            (f.first_token_t - f.submit_t) * 1e3 for f in futs_done
        )
        tpot = sorted(
            (b - a) * 1e3
            for f in futs_done
            for a, b in zip(f.token_times(), f.token_times()[1:])
        )
        n_tokens = sum(len(f.tokens_so_far()) for f in futs_done)

        def rpct(xs, q):
            p = _pct(xs, q)
            return round(p, 3) if p is not None else None

        out = {
            "streams": len(futs_done),
            "tokens": n_tokens,
            "tokens_per_s": round(n_tokens / wall, 1),
            "ttft_p50_ms": rpct(ttft, 0.50),
            "ttft_p95_ms": rpct(ttft, 0.95),
            "ttft_p99_ms": rpct(ttft, 0.99),
            "tpot_p50_ms": rpct(tpot, 0.50),
            "tpot_p95_ms": rpct(tpot, 0.95),
            "tpot_p99_ms": rpct(tpot, 0.99),
            "mean_batch_fill": round(
                eng.fill_sum / eng.n_rounds, 4
            ) if eng.n_rounds else None,
            "requeued": eng.n_requeued,
            "preempted": eng.n_preempted,
        }
        if spec:
            out["spec_k"] = spec_k
            out["accept_rate"] = round(
                eng.n_accepted / eng.n_proposed, 4
            ) if eng.n_proposed else None
        eng.stop()
        # Goodput twin of the serve line: useful token time vs the
        # waits (idle/queue/swap), from the same ledger the train plane
        # uses. Diffed against the pre-load totals so back-to-back
        # run_load calls (base then speculative) stay independent.
        gp_after = _gp.ledger().totals()
        gp = {
            k: gp_after[k] - gp_before.get(k, 0.0) for k in gp_after
        }
        useful = gp["compute"]
        waits = gp["serve_idle"] + gp["serve_queue"] + gp["serve_swap"]
        denom = useful + waits
        out["goodput"] = {
            "useful_token_time_s": round(useful, 3),
            "idle_s": round(gp["serve_idle"], 3),
            "queue_s": round(gp["serve_queue"], 3),
            "swap_s": round(gp["serve_swap"], 3),
            "useful_fraction": round(useful / denom, 4) if denom else None,
        }
        if not gp_was:
            _gp.disable()
        return out

    base = run_load(False)
    n_chips = jax.local_device_count()
    line = {
        "metric": "serve_decode",
        "model": "cachelm",
        "rows": rows,
        "workers": workers,
        "max_new_tokens": max_new,
        **base,
        "tokens_per_s_per_chip": round(base["tokens_per_s"] / n_chips, 1),
        "chips": n_chips,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
    }
    if spec_pair and spec_k > 0:
        q = run_load(True)
        q["speedup_vs_plain"] = (
            round(q["tokens_per_s"] / base["tokens_per_s"], 4)
            if base["tokens_per_s"]
            else None
        )
        line["speculative"] = q
    print(json.dumps(line), flush=True)


def bench_autotune(which="gpt2", trials=8, iters=12):
    """Closed-loop autotune tuned-vs-default pair in ONE run (one
    ``autotune_onoff`` JSON line, mirroring ``comm_overlap_onoff``).

    Runs the full worker-side loop (``make_train_step(autotune=...)``,
    driverless local search): trial 0 measures the hand-tuned default
    vector (the incumbent, exactly ``ParameterManager::Initialize``
    semantics), later trials follow GP-EI proposals, every trial scores
    a warmup-discarded window of real step wall time, and the search
    settles on the best *measured* vector — which therefore can never
    measure worse than the default it was seeded with. The line carries
    both window measurements (``step_ms_default``/``step_ms_tuned``)
    plus an independent post-convergence re-time of the winner.
    """
    from jax.sharding import NamedSharding

    from horovod_tpu import tune
    from horovod_tpu.parallel import dp

    ctx = hvd.init()
    n = hvd.size()
    params, batch_np, loss_fn, batch, seq = _bench_setup_for(which, n)
    sharding = NamedSharding(ctx.mesh, P(hvd.WORLD_AXIS))

    window, warmup = 4, 2
    cfg = tune.AutotuneConfig(
        window_steps=window, warmup_steps=warmup, max_trials=trials,
        patience=max(3, trials // 2),
    )
    step, opt = dp.make_train_step(
        loss_fn, optax.adamw(1e-4), autotune=cfg,
    )
    state = dp.init_state(jax.tree.map(jnp.array, params), opt)

    def repeat():
        while True:
            yield batch_np

    it = hvd.prefetch_to_device(repeat(), depth=2, sharding=sharding)
    # Budget: every trial costs warmup+window scored steps plus the
    # switch boundary's margin; 3x covers compile stalls on retraces.
    budget = 3 * (window + warmup + 2) * (trials + 2)
    for _ in range(budget):
        state, loss = step(state, next(it))
        if step.autotune.done:
            break
    if not np.isfinite(float(loss)):
        raise RuntimeError(f"non-finite loss in autotune bench: {loss}")

    search = step.autotune.source.search
    history = search.history()
    if not history:
        raise RuntimeError("autotune search recorded no trials in budget")
    step_ms_default = -history[0][1]  # trial 0 IS the default vector
    step_ms_tuned = -search.best_score
    best = search.best_vector()

    # Independent re-time of the settled winner (the wrapper no longer
    # blocks per step once the search is done, so time a drained loop).
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, next(it))
    jax.block_until_ready((state, loss))
    retimed_ms = (time.perf_counter() - t0) / iters * 1e3

    print(
        json.dumps(
            {
                "metric": "autotune_onoff",
                "model": which,
                "batch_per_chip": batch,
                "seq_len": seq,
                "trials": len(history),
                "converged": bool(step.autotune.done),
                "window_steps": window,
                "warmup_steps": warmup,
                "step_ms_default": round(step_ms_default, 3),
                "step_ms_tuned": round(step_ms_tuned, 3),
                "speedup": (
                    round(step_ms_default / step_ms_tuned, 4)
                    if step_ms_tuned else None
                ),
                "tuned_leq_default": step_ms_tuned <= step_ms_default,
                "best_vector": {k: (v if not isinstance(v, bool) else int(v))
                                for k, v in best.items()},
                "tuned_step_ms_retimed": round(retimed_ms, 3),
                "knobs": search.registry.names,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,
    )


def main():
    ctx = hvd.init()
    n = hvd.size()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    images = jnp.zeros((n * BATCH_PER_CHIP, IMAGE_SIZE, IMAGE_SIZE, 3), jnp.bfloat16)
    labels = jnp.zeros((n * BATCH_PER_CHIP,), jnp.int32)
    variables = model.init(rng, images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    opt_state = opt.init(params)

    wa = hvd.WORLD_AXIS

    def one_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images,
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            return loss, updates["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = opt.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        # BN stats averaged across replicas (SyncBN-style running stats).
        new_bs = hvd.fused_allreduce(new_bs, op=hvd.Average)
        return new_params, new_bs, new_opt, hvd.allreduce(loss)

    # No donation: donated outputs can change the argument signature and
    # force a recompile on the timed call; at these sizes the extra copy
    # is noise.
    @hvd.spmd(
        in_specs=(P(), P(), P(), P(wa), P(wa)),
        out_specs=(P(), P(), P(), P()),
    )
    def run_iters(params, batch_stats, opt_state, images, labels):
        def body(_, carry):
            p, bs, os_, _loss = carry
            return one_step(p, bs, os_, images, labels)

        init = (params, batch_stats, opt_state, jnp.zeros((), jnp.float32))
        return lax.fori_loop(0, ITERS, body, init)

    dt, dt_spread = _timed_loop(
        run_iters, (params, batch_stats, opt_state, images, labels), drain_idx=3
    )

    total_images = ITERS * n * BATCH_PER_CHIP
    img_per_sec = total_images / dt
    per_chip = img_per_sec / n
    step_ms = dt / ITERS * 1e3
    step_spread_ms = dt_spread / ITERS * 1e3

    # Raw-JAX control: same model/step, no framework (on one chip the
    # BN-stats average and loss allreduce are identity).
    raw_step_ms = None
    if n == 1:
        raw_opt = optax.sgd(0.1, momentum=0.9)

        def one_step_raw(carry, data):
            p, bs, os_, _loss = carry
            imgs, lbls = data

            def loss_fn(p):
                logits, updates = model.apply(
                    {"params": p, "batch_stats": bs},
                    imgs,
                    train=True,
                    mutable=["batch_stats"],
                )
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, lbls
                ).mean()
                return loss, updates["batch_stats"]

            (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            updates, new_os = raw_opt.update(grads, os_, p)
            return optax.apply_updates(p, updates), new_bs, new_os, loss

        raw_dt, raw_spread = _raw_jax_control(
            one_step_raw,
            (
                params,
                batch_stats,
                raw_opt.init(params),
                jnp.zeros((), jnp.float32),
            ),
            (images[:BATCH_PER_CHIP], labels[:BATCH_PER_CHIP]),
            ITERS,
            drain_idx=3,
        )
        raw_step_ms = raw_dt / ITERS * 1e3
        raw_spread_ms = raw_spread / ITERS * 1e3

    peak = _peak_tflops(jax.devices()[0])
    achieved_tflops = per_chip * ANALYTIC_FLOPS_PER_IMAGE / 1e12
    mfu = achieved_tflops / peak if np.isfinite(peak) else None

    print(
        json.dumps(
            {
                "metric": "resnet50_images_per_sec_per_chip",
                "value": round(per_chip, 2),
                "unit": "images/sec/chip",
                "vs_baseline": round(per_chip / BASELINE_IMG_PER_SEC_PER_DEVICE, 3),
                "raw_jax_step_ms": (
                    round(raw_step_ms, 2) if raw_step_ms else None
                ),
                "raw_jax_step_ms_spread": (
                    round(raw_spread_ms, 2) if raw_step_ms else None
                ),
                "framework_overhead_pct": (
                    _overhead_pct(step_ms, raw_step_ms)
                    if raw_step_ms
                    else None
                ),
                "step_time_ms": round(step_ms, 2),
                "step_ms_spread": round(step_spread_ms, 2),
                "timing_windows": N_WINDOWS,
                "batch_per_chip": BATCH_PER_CHIP,
                "mfu": round(mfu, 4) if mfu is not None else None,
                "analytic_tflops_per_chip": round(achieved_tflops, 1),
                "peak_tflops_bf16": peak if np.isfinite(peak) else None,
                "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
                "n_chips": n,
            }
        ),
        flush=True,  # survives a driver timeout killing the next model's compile
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--model",
        choices=["all", "resnet50", "bert", "gpt2", "mlp"],
        default="all",
        help="default 'all' prints one JSON line per headline model "
        "(ResNet-50 + BERT + GPT-2) so the driver-captured artifact "
        "records every number the README claims (VERDICT r3 #9); "
        "'mlp' is a CPU-smoke model valid only with --overlap",
    )
    ap.add_argument(
        "--overlap",
        action="store_true",
        help="run the overlap on/off pair for --model (gpt2 when 'all'/"
        "'resnet50') and emit ONE comm_overlap_onoff JSON line instead "
        "of the headline lines",
    )
    ap.add_argument(
        "--accum-steps",
        type=int,
        default=4,
        help="microbatch count for the --overlap pair (accum_steps=K "
        "in make_train_step; wire bytes are K-invariant)",
    )
    ap.add_argument(
        "--quant",
        choices=["int8", "fp8"],
        default=None,
        help="run the quantized-collective on/off pair for --model "
        "(gpt2 when 'all'/'resnet50') and emit ONE quant_onoff JSON "
        "line; composes with --overlap --accum-steps K",
    )
    ap.add_argument(
        "--fp8",
        action="store_true",
        help="run the fp8 training-matmul on/off pair (compute dtype, "
        "NOT the --quant wire format) and emit ONE fp8_onoff JSON line "
        "(step-time pair + the fp8-loss-tracks-fp32 convergence gate; "
        "exits nonzero when fp8 training diverges)",
    )
    ap.add_argument(
        "--act-quant",
        action="store_true",
        help="run the int8 activation-storage on/off pair on an "
        "activation-dominated MLP and emit ONE act_quant_onoff JSON "
        "line (step-time pair + planner-predicted peak saving + the "
        "predicted-vs-measured gate under HVDTPU_MEMPLAN_TOLERANCE)",
    )
    ap.add_argument(
        "--fused-update",
        action="store_true",
        help="run the fused optimizer-update on/off pair for --model "
        "(gpt2 when 'all'/'resnet50') and emit ONE fused_update_onoff "
        "JSON line (ZeRO-1 sharded step, fused Pallas pass vs the "
        "unfused optax chain)",
    )
    ap.add_argument(
        "--remat",
        nargs="?",
        const="dots_saveable",
        default=None,
        metavar="POLICY",
        help="run the selective-remat on/off pair for --model (gpt2 "
        "when 'all'/'resnet50') and emit ONE remat_onoff JSON line "
        "(default policy dots_saveable)",
    )
    ap.add_argument(
        "--guard",
        action="store_true",
        help="run the gradient-guard on/off pair for --model (gpt2 when "
        "'all'/'resnet50') and emit ONE guard_onoff JSON line (the "
        "fail-silent defense's < 1%% step-time budget)",
    )
    ap.add_argument(
        "--autotune",
        action="store_true",
        help="run the closed-loop autotuner for --model (gpt2 when "
        "'all'/'resnet50') and emit ONE autotune_onoff JSON line "
        "(tuned-vs-default step time over the searched knob vector); "
        "with --serve, tunes the serving pool's batch timeout/"
        "watermarks against p95 under the closed-loop load instead",
    )
    ap.add_argument(
        "--autotune-trials", type=int, default=8,
        help="trial budget for --autotune",
    )
    ap.add_argument(
        "--trace",
        action="store_true",
        help="run the tracing-plane on/off pair for --model (gpt2 when "
        "'all'/'resnet50') and emit ONE trace_onoff JSON line (the span "
        "recorder's < 2%% CPU-smoke overhead budget is enforced)",
    )
    ap.add_argument(
        "--goodput",
        action="store_true",
        help="run a short instrumented loop with the goodput ledger "
        "armed and emit ONE goodput JSON line (per-category wall-clock "
        "seconds, goodput fraction, conservation residual)",
    )
    ap.add_argument(
        "--serve",
        action="store_true",
        help="closed-loop load against the in-process serving pool "
        "(horovod_tpu.serve) and emit ONE serve_latency JSON line "
        "(throughput + p50/p95/p99 request latency)",
    )
    ap.add_argument(
        "--serve-workers", type=int, default=2,
        help="serving pool size for --serve",
    )
    ap.add_argument(
        "--serve-batch", type=int, default=8,
        help="device batch size for --serve",
    )
    ap.add_argument(
        "--serve-requests", type=int, default=512,
        help="total closed-loop requests for --serve",
    )
    ap.add_argument(
        "--decode",
        action="store_true",
        help="run closed-loop streaming load against the token-level "
        "decode engine (paged KV cache + continuous batching) and emit "
        "ONE serve_decode JSON line with a speculative on/off pair "
        "(use with --serve: 'bench.py --serve --decode')",
    )
    ap.add_argument(
        "--decode-streams", type=int, default=32,
        help="total closed-loop streams for --decode",
    )
    ap.add_argument(
        "--decode-tokens", type=int, default=32,
        help="max new tokens per stream for --decode",
    )
    ap.add_argument(
        "--decode-rows", type=int, default=4,
        help="fixed decode batch rows per worker for --decode",
    )
    ap.add_argument(
        "--decode-spec-k", type=int, default=3,
        help="draft proposals per speculative round for the --decode "
        "pair (0 skips the speculative leg)",
    )
    ap.add_argument(
        "--out-dir", default=None,
        help="directory for what --trace / --goodput write (span dumps, "
        "the checkpoint); default: a fresh temporary directory",
    )
    args = ap.parse_args()
    which = args.model
    enable_compile_cache()

    # --fused-update, --remat, --fp8 and --act-quant compose (one JSON
    # line each); the remaining modes keep their historical
    # one-line-per-run exclusivity.
    ran_kernel_pair = False
    if args.fp8:
        bench_fp8()
        ran_kernel_pair = True
    if args.act_quant:
        bench_act_quant()
        ran_kernel_pair = True
    if args.fused_update:
        fu_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_fused_update(fu_model)
        ran_kernel_pair = True
    if args.remat:
        from horovod_tpu.ops.remat import resolve_policy

        if not resolve_policy(args.remat)[0]:
            raise SystemExit(
                f"--remat {args.remat} is a no-op policy; the pair would "
                "time none-vs-none"
            )
        rm_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_remat(rm_model, policy=args.remat)
        ran_kernel_pair = True
    if ran_kernel_pair:
        pass
    elif args.trace:
        trace_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_trace(trace_model, out_dir=args.out_dir)
    elif args.guard:
        guard_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_guard(guard_model)
    elif args.goodput:
        gp_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_goodput(gp_model, out_dir=args.out_dir)
    elif args.serve or args.decode:
        if args.decode:
            bench_decode(
                streams=args.decode_streams,
                max_new=args.decode_tokens,
                rows=args.decode_rows,
                workers=args.serve_workers,
                spec_k=args.decode_spec_k,
            )
        else:
            bench_serve(
                batch_size=args.serve_batch,
                workers=args.serve_workers,
                requests=args.serve_requests,
                autotune=args.autotune,
            )
    elif args.autotune:
        tune_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_autotune(tune_model, trials=args.autotune_trials)
    elif args.quant:
        quant_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_quant(
            quant_model,
            quant=args.quant,
            accum_steps=args.accum_steps if args.overlap else 1,
            overlap=args.overlap,
        )
    elif args.overlap:
        overlap_model = which if which in ("bert", "gpt2", "mlp") else "gpt2"
        bench_overlap(overlap_model, accum_steps=args.accum_steps)
    elif which == "mlp":
        raise SystemExit("--model mlp is only meaningful with --overlap")
    else:
        if which in ("all", "resnet50"):
            main()
        if which in ("all", "bert"):
            bench_bert()
        if which in ("all", "gpt2"):
            bench_gpt2()
