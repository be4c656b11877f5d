#!/usr/bin/env python
"""chip_smoke — the training main path, once, on the chip.

    python chip_smoke.py              one chip (what the driver runs)
    python chip_smoke.py --chips 4    the path across chips, and only it

One chip: GPT-2-small exactly as ``GPT2Config.small()`` gives it (12 layers,
d_model 768, 12 heads x 64, d_ff 3072, vocab 50,257, context 1024, bf16, tied
head), batch 16 x 1024 tokens drawn from ``--seed``, AdamW, through the entry
points a user calls and with every default:

1. ``hvd.init()`` -> ``dp.make_train_step(loss_fn, optax.adamw(...))`` ->
   ``dp.init_state`` -> a few steps, one host dispatch per step, each closed
   by ``jax.block_until_ready``;
2. the README quick-start path on the same model and batch: ``hvd.spmd`` +
   ``hvd.DistributedOptimizer``.

Before them, the one kernel PR 22 had to change (fused AdamW) runs compiled
against its pure-jax twin. Four chips: a one-device reference, data parallel
over four, ZeRO-1 over four, and data parallel at the full per-chip shape.

Every line of stdout is one JSON object; the last is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure — no TPU, a device kind with no peak on record, a compiled step
with no Pallas kernel in it, a phase that raises, a loss that is not finite
or does not fall — prints ``{"ok": false, "reason": ...}`` and exits 1.
Times printed here are smoke readings, not benchmark numbers. One process,
no children: the process that prints the last line is the one that held the
chip.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import logging
import math
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.gpt2 import GPT2Config, GPT2LMModel
from horovod_tpu.obs.flops import peak_tflops
from horovod_tpu.ops.fusion import FlatBuckets
from horovod_tpu.optimizer import FusedAdamSpec, fused_adamw_update
from horovod_tpu.parallel import dp
from horovod_tpu.utils.compile_cache import enable_compile_cache

BATCH = 16  # sequences, per chip at the full shape
STEPS = 4
LEARNING_RATE = 3e-4
# Two XLA programs for the same bf16 math (another builder, other compiler
# options, a gradient averaged over four shards) round differently; after
# STEPS AdamW steps their losses agree to a fraction of a percent. A wrong
# gradient or a shard that never joined the reduction is off by far more.
LOSS_REL_TOL = 1e-2
# tests/test_fused_update.py holds the fused AdamW math to optax at
# rtol=2e-6 (about 17 fp32 ulp) on the CPU. Mosaic and XLA do not share an
# fp32 divide/sqrt, so on the chip the kernel and its twin sit a few ulp
# apart, not zero. The update is a sum (Adam term + weight decay) that can
# cancel, so an element's own magnitude is no yardstick: the same 2e-6 is
# taken against the output's largest magnitude.
FUSED_ADAMW_REL_TOL = 2e-6

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


class SmokeFailure(Exception):
    """The run is not a pass; the message is the reason."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check_device(dev) -> float:
    """A chip run or nothing: a TPU whose kind has a peak on record."""
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: jax.devices()[0].platform is {dev.platform!r}"
        )
    return peak_tflops(dev)  # raises for a kind not in PEAK_TFLOPS_BF16


def check_pallas_calls(hlo: str, what: str) -> int:
    """Compiled Mosaic kernels in ``hlo``; none means a Pallas path was
    quietly replaced (flash attention by XLA attention, a kernel by the
    interpreter)."""
    n = hlo.count('custom_call_target="tpu_custom_call"')
    if n == 0:
        raise SmokeFailure(f"{what}: no tpu_custom_call in the compiled HLO")
    return n


def check_losses(losses, what: str) -> None:
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"{what}: loss did not fall: {losses}")


def check_agree(a: float, b: float, what: str) -> float:
    rel = abs(a - b) / abs(b)
    if rel > LOSS_REL_TOL:
        raise SmokeFailure(
            f"{what}: final losses {a} vs {b} differ by {rel:.2e} "
            f"(tolerance {LOSS_REL_TOL})"
        )
    return rel


def count_collectives(lowered, compiled_hlo: str) -> dict:
    """Collectives by op: ``requested`` is what the program hands the
    compiler (StableHLO — the framework's bucket policy), ``compiled`` what
    the compiled HLO text holds after the compiler's own lowering (async
    pairs count once, at their ``-start``; an op the compiler fused into
    several consumers is printed, and counted, once per consumer)."""
    stablehlo = lowered.as_text()
    return {
        "requested": {
            op: stablehlo.count(f"stablehlo.{op.replace('-', '_')}")
            for op in _COLLECTIVES
        },
        "compiled": {
            op: len(re.findall(rf" {op}(?:-start)?\(", compiled_hlo))
            for op in _COLLECTIVES
        },
    }


class _CompileCounter:
    """Compilations since the last ``take()``: how many programs went to
    the backend compiler or the persistent cache, and how the cache
    answered."""

    def __init__(self):
        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {
            "compile_requests": self.requests, "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
        self.requests = self.hits = self.misses = 0
        return out


def lower_and_compile(lower, cache) -> dict:
    """Trace + lower, then compile, timed apart: the persistent cache can
    only shorten the second. Returns the fields every step phase prints,
    plus ``hlo``/``lowered`` for the checks."""
    cache.take()
    t0 = time.perf_counter()
    lowered = lower()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    return {
        "lowered": lowered,
        "hlo": compiled.as_text(),
        "fields": {
            "lower_s": round(t1 - t0, 2),
            "compile_s": round(t2 - t1, 2),
            "compile": cache.take(),
            # What the compiler planned per device; the runtime's
            # peak_bytes_in_use below counts live arrays only.
            "compiled_memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
            },
        },
    }


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device (None where the backend keeps no
    such statistic). A process-wide high-water mark: it never falls."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


def init_world(devices=None) -> str:
    """``hvd.init()``, returning what it logged about the devices it found
    (with ``devices=`` it is told, and logs nothing)."""
    records = []
    handler = logging.Handler(level=logging.INFO)
    handler.emit = lambda rec: records.append(rec.getMessage())
    log = logging.getLogger("horovod_tpu")
    old_level = log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        if hvd.is_initialized():
            hvd.shutdown()
        hvd.init(devices)
    finally:
        log.removeHandler(handler)
        log.setLevel(old_level)
    found = [r for r in records if r.startswith("hvd.init(): found")]
    if devices is None:
        dev = jax.devices()[0]
        want = f"{len(jax.devices())} {dev.platform} device(s)"
        if len(found) != 1 or want not in found[0]:
            raise SmokeFailure(
                f"hvd.init() did not say it found {want}: {records}"
            )
    return found[0] if found else ""


def make_problem(cfg: GPT2Config, n_seqs: int, seed: int):
    """Model, loss, a params factory and one fixed batch, all from
    ``seed``. The factory re-draws the SAME params on each call: the dp
    step donates its state, so every phase needs its own copy."""
    model = GPT2LMModel(cfg)
    k_params, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(
        k_tokens, (n_seqs, cfg.max_len + 1), 0, cfg.vocab_size, jnp.int32
    )

    init = jax.jit(lambda key, toks: model.init(key, toks)["params"])

    def make_params():
        return init(k_params, tokens[:1, :-1])

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch[:, 1:]
        ).mean()

    return loss_fn, make_params, tokens


def describe(cfg: GPT2Config, params) -> dict:
    return {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "max_len": cfg.max_len,
        "dtype": jnp.dtype(cfg.dtype).name,
        "n_params": sum(int(x.size) for x in jax.tree.leaves(params)),
    }


def run_steps(step_once, carry, n: int):
    """``n`` host-dispatched steps, each closed by block_until_ready.
    ``step_once(carry) -> (carry, loss)``."""
    losses, step_ms = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        carry, loss = step_once(carry)
        jax.block_until_ready((carry, loss))
        step_ms.append(round((time.perf_counter() - t0) * 1e3, 3))
        losses.append(float(loss))
    return carry, losses, step_ms


def phase_fused_adamw(seed: int) -> None:
    """The repaired kernel (scalar ``powf`` moved out of Mosaic), compiled,
    against its pure-jax twin on a ragged few-million-element buffer."""
    n = 4 * 1024 * 1024 + 1000
    rng = np.random.RandomState(seed)
    p = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n) * 0.01, jnp.float32)
    v = jnp.asarray(np.abs(rng.randn(n)) * 1e-3, jnp.float32)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    spec = FusedAdamSpec(1e-3)
    outs = {}
    for impl in ("jax", "pallas"):
        fn = jax.jit(
            lambda p, m, v, g, c, impl=impl: fused_adamw_update(
                p, m, v, g, c, spec, impl=impl
            )
        )
        compiled = fn.lower(p, m, v, g, 3).compile()
        if impl == "pallas":
            n_calls = check_pallas_calls(compiled.as_text(), "fused_adamw")
        outs[impl] = [np.asarray(x) for x in fn(p, m, v, g, 3)]
    max_diff, max_value, ratio = {}, {}, {}
    for name, a, b in zip(("update", "m", "v"), outs["jax"], outs["pallas"]):
        max_diff[name] = float(np.abs(a - b).max())
        max_value[name] = float(np.abs(a).max())
        ratio[name] = max_diff[name] / max_value[name]
    emit(
        phase="fused_adamw_kernel", n=n, tpu_custom_calls=n_calls,
        max_abs_diff=max_diff, max_abs_value=max_value,
        diff_over_max_value=ratio, tolerance=FUSED_ADAMW_REL_TOL,
    )
    worst = max(ratio.values())
    if not worst <= FUSED_ADAMW_REL_TOL:
        raise SmokeFailure(
            f"fused_adamw kernel differs from its jax twin by {worst:.3e} "
            f"of the output's largest magnitude (tolerance "
            f"{FUSED_ADAMW_REL_TOL})"
        )


def phase_dp(name, cfg, loss_fn, make_params, tokens, cache, *,
             steps=STEPS, **step_kwargs):
    """``dp.make_train_step`` on the current world; returns
    ``(losses, final_state)``."""
    mesh = hvd.mesh()
    step, opt = dp.make_train_step(
        loss_fn, optax.adamw(LEARNING_RATE), **step_kwargs
    )
    params = make_params()
    model = describe(cfg, params)
    state = dp.init_state(params, opt)
    batch = jax.device_put(tokens, NamedSharding(mesh, P(hvd.WORLD_AXIS)))
    shard_devices = {s.device for s in batch.addressable_shards}
    if len(shard_devices) != hvd.size():
        raise SmokeFailure(
            f"{name}: batch shards sit on {len(shard_devices)} device(s), "
            f"world is {hvd.size()}"
        )
    built = lower_and_compile(lambda: step.lower(state, batch), cache)
    hlo = built["hlo"]
    n_calls = check_pallas_calls(hlo, name)
    collectives = count_collectives(built["lowered"], hlo)
    if hvd.size() > 1 and not collectives["compiled"]["all-reduce"]:
        raise SmokeFailure(f"{name}: no all-reduce in the compiled HLO")
    state, losses, step_ms = run_steps(
        lambda st: step(st, batch), state, steps
    )
    emit(
        phase=name, model=model, world=hvd.size(),
        global_batch=int(tokens.shape[0]), seq=cfg.max_len,
        **built["fields"], compiles_inside_steps=cache.take(),
        tpu_custom_calls=n_calls, collectives=collectives,
        losses=losses, step_ms_smoke_reading=step_ms,
        peak_bytes_in_use=peak_bytes(mesh.devices.flat),
    )
    check_losses(losses, name)
    return losses, state


def phase_spmd(name, cfg, loss_fn, make_params, tokens, cache):
    """The README quick-start: ``hvd.spmd`` + ``DistributedOptimizer``."""
    opt = hvd.DistributedOptimizer(optax.adamw(LEARNING_RATE))
    wa = hvd.WORLD_AXIS

    @hvd.spmd(in_specs=(P(), P(), P(wa)), out_specs=(P(), P(), P()))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (
            optax.apply_updates(params, updates), opt_state,
            hvd.allreduce(loss),
        )

    params = make_params()
    model = describe(cfg, params)
    opt_state = opt.init(params)
    built = lower_and_compile(
        lambda: train_step.lower(params, opt_state, tokens), cache
    )
    hlo = built["hlo"]
    n_calls = check_pallas_calls(hlo, name)

    def step_once(carry):
        p, o, loss = train_step(*carry, tokens)
        return (p, o), loss

    _, losses, step_ms = run_steps(step_once, (params, opt_state), STEPS)
    emit(
        phase=name, model=model, world=hvd.size(),
        global_batch=int(tokens.shape[0]), seq=cfg.max_len,
        **built["fields"], compiles_inside_steps=cache.take(),
        tpu_custom_calls=n_calls,
        collectives=count_collectives(built["lowered"], hlo),
        losses=losses, step_ms_smoke_reading=step_ms,
        peak_bytes_in_use=peak_bytes(hvd.mesh().devices.flat),
    )
    check_losses(losses, name)
    return losses


def smoke_one_chip(cfg: GPT2Config, seed: int, cache) -> None:
    n = len(jax.devices())
    if n != 1:
        # hvd.init() with every default spans all devices JAX finds; this
        # mode is the one-chip path and does not spread over a larger host.
        raise SmokeFailure(
            f"one-chip mode needs exactly 1 device, jax found {n}: run "
            "--chips 4 on a four-chip host"
        )
    emit(phase="init", log=init_world(), size=hvd.size())
    phase_fused_adamw(seed)
    loss_fn, make_params, tokens = make_problem(cfg, BATCH, seed)
    dp_losses, state = phase_dp(
        "dp.make_train_step", cfg, loss_fn, make_params, tokens, cache
    )
    del state  # 1.5 GB of params + moments the next phase does not need
    spmd_losses = phase_spmd(
        "hvd.spmd+DistributedOptimizer", cfg, loss_fn, make_params, tokens,
        cache,
    )
    rel = check_agree(
        spmd_losses[-1], dp_losses[-1], "hvd.spmd vs dp.make_train_step"
    )
    emit(
        phase="agreement", spmd_vs_dp_final_loss_rel_diff=rel,
        tolerance_rel=LOSS_REL_TOL,
    )


def check_opt_state_sharded(state, world: int) -> dict:
    """ZeRO-1: every flat optimizer-state bucket is split in ``world``
    equal dim-0 shards that live on ``world`` distinct devices."""
    buckets = [
        b for fb in jax.tree.leaves(
            state.opt_state, is_leaf=lambda x: isinstance(x, FlatBuckets)
        ) if isinstance(fb, FlatBuckets) for b in fb.buffers
    ]
    if not buckets:
        raise SmokeFailure("zero1: no FlatBuckets in the optimizer state")
    for b in buckets:
        shards = b.addressable_shards
        devices = {s.device for s in shards}
        sizes = {s.data.shape[0] for s in shards}
        if len(devices) != world or sizes != {b.shape[0] // world}:
            raise SmokeFailure(
                f"zero1: a {b.shape} bucket has shard sizes {sizes} on "
                f"{len(devices)} device(s); want {b.shape[0] // world} on "
                f"{world}"
            )
    return {
        "n_buckets": len(buckets),
        "bucket_elems": [int(b.shape[0]) for b in buckets],
        "shard_elems": [int(b.shape[0]) // world for b in buckets],
    }


def smoke_four_chips(cfg: GPT2Config, seed: int, cache) -> None:
    """Only the path across chips and what it is compared with."""
    n = len(jax.devices())
    if n != 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, jax found {n}")
    loss_fn, make_params, tokens = make_problem(cfg, BATCH, seed)

    init_world(jax.devices()[:1])
    ref_losses, state = phase_dp(
        "reference_1dev", cfg, loss_fn, make_params, tokens, cache
    )
    del state

    emit(phase="init", log=init_world(), size=hvd.size())
    if hvd.size() != 4:
        raise SmokeFailure(f"hvd.size() is {hvd.size()}, want 4")
    dp_losses, state = phase_dp(
        "dp4", cfg, loss_fn, make_params, tokens, cache
    )
    del state
    zero_losses, state = phase_dp(
        "zero1", cfg, loss_fn, make_params, tokens, cache, sharded=True
    )
    emit(phase="zero1_state", **check_opt_state_sharded(state, 4))
    del state
    emit(
        phase="agreement",
        dp4_vs_reference_final_loss_rel_diff=check_agree(
            dp_losses[-1], ref_losses[-1], "dp4 vs reference"
        ),
        zero1_vs_reference_final_loss_rel_diff=check_agree(
            zero_losses[-1], ref_losses[-1], "zero1 vs reference"
        ),
        tolerance_rel=LOSS_REL_TOL,
    )

    full = make_problem(cfg, BATCH * 4, seed)
    phase_dp("dp4_full_shape", cfg, *full, cache, steps=3)


def run(chips: int, seed: int, cfg: GPT2Config) -> dict:
    """All phases for ``chips``; returns the device record of the last
    line. Raises on any failure."""
    cache_dir = enable_compile_cache()
    cache = _CompileCounter()
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    emit(
        phase="env", chips=chips, seed=seed,
        jax=jax.__version__,
        jaxlib=importlib.metadata.version("jaxlib"),
        libtpu=importlib.metadata.version("libtpu"),
        device=device, peak_tflops_bf16=check_device(dev),
        compile_cache_dir=cache_dir,
    )
    if chips == 4:
        smoke_four_chips(cfg, seed, cache)
    else:
        smoke_one_chip(cfg, seed, cache)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the path across four chips and its reference",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = run(args.chips, args.seed, GPT2Config.small())
    except BaseException as e:
        # The one handler on the path: say why, then fail. Nothing is
        # swallowed — the traceback goes to stderr and the exit code is 1.
        emit(ok=False, reason=f"{type(e).__name__}: {e}")
        raise
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
