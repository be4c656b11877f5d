#!/usr/bin/env python
"""chaos_soak — scripted fault schedules over the elastic launcher.

Each scenario runs a small deterministic elastic training job (the
per-step update is a pure function of the step number, so the final
parameters are world-size- and restart-invariant) under one armed
``HVDTPU_CHAOS`` schedule, and asserts the *recovery invariants*:

* the job finishes rc=0 without human intervention;
* rank 0 reaches exactly the target step count;
* the final parameters equal the fault-free baseline's bit-for-bit
  analytic value (no step lost, none double-applied);
* scenario-specific evidence that the fault actually fired and the
  intended recovery path (not a lucky accident) absorbed it.

Scenarios (the fault catalog the elastic stack claims to survive):

==============  ========================================================
``crash``       a worker hard-exits mid-commit → driver blacklists,
                republishes; survivor restores committed state
``hang``        a worker freezes (heartbeat included) → heartbeat lease
                expiry kills/blacklists it mid-round, not the drain
``kv_outage``   sustained KV request failures → client retry + guarded
                polling absorb them; nobody restarts
``ckpt``        the newest checkpoint is bit-rotted, then every worker
                dies → restore quarantines it and falls back one step;
                blacklist cooldown re-admits the host
``straggler``   one rank runs slow every step → lockstep collectives
                stretch but the job completes with no false failure
``quant``       int8+error-feedback training crashes mid-run → resume
                restores the FULL TrainState (incl. EF residuals) and
                the final params are bit-identical to the fault-free
                quantized baseline (run automatically for comparison)
``serve``       a serving worker is hard-killed mid-flight → its leased
                requests re-queue to the survivor (zero dropped), the
                host respawns from blacklist probation, and the
                response count/values match the fault-free run exactly
``decode``      a token-level decode worker is killed MID-SEQUENCE
                (``serve.decode:crash``) under closed-loop streaming
                load → every in-flight stream resumes from prompt +
                committed tokens on the survivor, finals token-identical
                to the fault-free run, ``n_requeued > 0``
``stream``      live weight streaming under fire: an elastic trainer
                publishes per-step weight versions through the
                journaled KV into an in-process decode fleet; the
                publisher host is hard-killed mid-publish (torn set on
                the wire), the driver dies and is adopted, a stale-epoch
                manifest is injected post-mortem, and the stream is
                finally starved into the CheckpointWatcher fallback →
                the fleet never applies a torn set, stale epochs are
                rejected, finals are token-identical to the fault-free
                twin (``stream_baseline``)
``preempt``     a worker receives a real SIGTERM eviction notice → it
                finishes the in-flight step, takes a manifest-verified
                priority checkpoint, and drains out through a shrunken
                round — departed, never blacklisted
``kv_server_crash``  the rendezvous KV listener is torn down hard
                mid-run (repeatedly) and re-listened from the journal
                replay on the same port — workers ride it out on
                client retries + reconnect epochs, zero restarts
``driver_crash``  the driver dies in round 2 (after real blacklist
                history accrued); a fresh ``--adopt`` driver replays
                the journal, re-attaches the orphaned live workers by
                pid, and finishes the job — same strikes, zero
                healthy-worker restarts
``silent``      fail-silent faults against a 3-rank guarded jax world:
                a NaN-poisoned batch is skipped in-graph on every rank
                (no step lost — the pipeline retries), ONE flipped
                param bit on one rank is caught by the checksum audit,
                localized by majority vote, reported to the driver's
                health scoring and healed by broadcast-resync; no
                corrupted step is ever committed to a checkpoint and
                the final params are bit-identical to the fault-free
                baseline
==============  ========================================================

Every scenario runs under a hard wall-clock deadline; on timeout the
harness dumps diagnostics (worker/driver log tails + the KV plane's
round/heartbeat/guard state), tears the wedged job down, and merges the
per-process flight-recorder dumps (``horovod_tpu.obs.trace`` — armed
for every scenario) into one clock-aligned "who was where" timeline
attached to the diagnostics, instead of hanging the whole soak.

Usage::

    python tools/chaos_soak.py                    # all scenarios
    python tools/chaos_soak.py --scenario crash --steps 6
    python tools/chaos_soak.py --json

Importable: ``tests/test_chaos.py`` runs one scenario in the fast tier
and the full soak in the slow tier through :func:`run_scenario` /
:func:`run_all`.
"""

from __future__ import annotations

import argparse
import json
import os
import stat
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEFAULT_STEPS = 8
LEARNING_RATE = 0.1
GRAD = 0.5  # allreduce(full(0.5))/size == 0.5 at any world size

# The training body every scenario runs: per-step update is a pure
# function of the step, checkpointed every step by rank 0, resumable
# from disk when a full restart loses in-memory state. Every rank exits
# at the target step (the blocking collectives keep them in lockstep),
# so a slow rank delays but never orphans its peers.
WORKER = '''
import json, os, sys, time
import numpy as np

import horovod_tpu.native as native
from horovod_tpu import elastic
from horovod_tpu import checkpoint as ckptlib

workdir = os.environ["HVDTPU_TEST_WORKDIR"]
host_id = os.environ["HVDTPU_HOST_ID"]
STEPS = int(os.environ["HVDTPU_TEST_SOAK_STEPS"])
CKDIR = os.path.join(workdir, "ckpt")


def log(rec):
    with open(os.path.join(workdir, "progress.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\\n")


native.init()
state = elastic.ObjectState(step=0, w=np.zeros(4, np.float64))
try:
    target = {"step": np.int64(0), "w": np.zeros(4, np.float64)}
    restored = ckptlib.restore_checkpoint(CKDIR, target)
    state.step = int(restored["step"])
    state.w = np.asarray(restored["w"])
    state.save()
    log({"host": host_id, "resumed_at": state.step})
except FileNotFoundError:
    pass

# Preemption grace: if this worker ever receives a SIGTERM eviction
# notice, its first post-notice commit writes a manifest-verified
# priority checkpoint of ITS state before the drain walks it out of
# the world (no-op for every scenario that never delivers one).
from horovod_tpu.elastic import worker as _ew


def _priority_ckpt():
    ckptlib.priority_checkpoint(
        os.path.join(workdir, "preempt_ckpt"),
        {"step": np.int64(state.step), "w": np.asarray(state.w)},
        step=int(state.step),
    )
    log({"host": host_id, "preempt_ckpt": int(state.step)})


_ew.register_preempt_callback(_priority_ckpt)


@elastic.run
def train(st):
    while st.step < STEPS:
        g = np.asarray(
            native.allreduce(
                np.full(4, %(grad)r, np.float32), name="grad"
            ),
            dtype=np.float64,
        ) / native.size()
        st.w = st.w - %(lr)r * g
        st.step += 1
        if native.rank() == 0:
            ckptlib.save_checkpoint(
                CKDIR,
                {"step": np.int64(st.step), "w": np.asarray(st.w)},
                step=st.step, keep=STEPS + 1,
            )
        log({"host": host_id, "rank": native.rank(),
             "size": native.size(), "step": st.step})
        st.commit()
    return st.step


train(state)
log({"host": host_id, "rank": native.rank(), "final_step": state.step,
     "final_w": [float(x) for x in np.asarray(state.w)]})
native.shutdown()
''' % {"grad": GRAD, "lr": LEARNING_RATE}


# Quantized-collective convergence worker (the `quant` scenario): a tiny
# deterministic jax training loop through dp.make_train_step with the
# int8 wire + error feedback, checkpointing the FULL TrainState (params,
# optimizer state, EF residuals) every step. Batches are a pure function
# of the step number, so an interrupted-and-resumed run must land on
# BIT-IDENTICAL final params vs the fault-free baseline — which only
# holds if the EF residual state round-trips through the checkpoint (a
# resume that zeroed the residuals would inject the lost error mass and
# diverge the remaining steps).
QUANT_WORKER = '''
import json, os
import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
import horovod_tpu.native as native
from horovod_tpu import checkpoint as ckptlib
from horovod_tpu import elastic
from horovod_tpu.ops.compression import Compression
from horovod_tpu.parallel import dp

workdir = os.environ["HVDTPU_TEST_WORKDIR"]
host_id = os.environ.get("HVDTPU_HOST_ID", "localhost")
STEPS = int(os.environ["HVDTPU_TEST_SOAK_STEPS"])
CKDIR = os.path.join(workdir, "ckpt")


def log(rec):
    with open(os.path.join(workdir, "progress.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\\n")


def residual_norm(ts):
    return float(
        np.sqrt(
            sum(
                float(jnp.sum(b.astype(jnp.float32) ** 2))
                for b in ts.opt_state.residual.buffers
            )
        )
    )


native.init()
hvd.init(devices=jax.devices("cpu")[:1])


def params0():
    rng = np.random.RandomState(0)
    return {
        "w": jnp.asarray(rng.randn(8, 4) * 0.5, jnp.float32),
        "b": jnp.zeros((4,), jnp.float32),
    }


def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)


def batch_for(step):
    rng = np.random.RandomState(1000 + step)
    return (
        jnp.asarray(rng.randn(16, 8), jnp.float32),
        jnp.asarray(rng.randn(16, 4), jnp.float32),
    )


# Coarse block (one scale across the whole bucket) so quantization error
# is substantial and the EF residuals carry real mass between steps.
step_fn, opt = dp.make_train_step(
    loss_fn, optax.sgd(0.05),
    compression=Compression.int8.with_block(64), donate=False,
)
box = {"ts": dp.init_state(params0(), opt)}
state = elastic.ObjectState(step=0)
try:
    box["ts"] = ckptlib.restore_checkpoint(CKDIR, box["ts"])
    state.step = int(box["ts"].step)
    state.save()
    log({
        "host": host_id,
        "resumed_at": state.step,
        "resume_residual_norm": residual_norm(box["ts"]),
    })
except FileNotFoundError:
    pass


@elastic.run
def train(st):
    while st.step < STEPS:
        ts, loss = step_fn(box["ts"], batch_for(st.step))
        box["ts"] = ts
        st.step = int(ts.step)
        ckptlib.save_checkpoint(CKDIR, ts, step=st.step, keep=STEPS + 1)
        log({"host": host_id, "rank": native.rank(), "size": native.size(),
             "step": st.step, "loss": float(loss)})
        st.commit()
    return st.step


train(state)
final = jax.device_get(box["ts"])
log({
    "host": host_id,
    "rank": native.rank(),
    "final_step": int(final.step),
    "final_w": [float(x) for x in np.asarray(final.params["w"]).reshape(-1)],
    "final_residual_norm": residual_norm(box["ts"]),
})
native.shutdown()
'''


# Fail-silent scenario worker (the `silent` scenario): a 3-rank elastic
# world where each process trains the SAME deterministic jax model
# through dp.make_train_step(guard=...) — batches are a pure function of
# the step, so every replica's state must stay bit-identical (the
# Horovod replication invariant). The chaos plane then breaks exactly
# that: `grad.nan` poisons one batch element on EVERY rank (the guard
# must skip the step in-graph, params/opt-state untouched, and the
# deterministic pipeline retries it), and `grad.bitflip` flips one
# seeded bit of ONE rank's params post-commit (only the consistency
# audit can see it — majority vote localizes the rank, broadcast-resync
# heals it, the driver's health scoring records the report). Rank 0
# checkpoints every committed step AFTER the audit, so no corrupted
# state can ever reach disk.
SILENT_WORKER = '''
import json, os
import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
import horovod_tpu.native as native
from horovod_tpu import checkpoint as ckptlib
from horovod_tpu import elastic
from horovod_tpu.guard import GuardConfig
from horovod_tpu.parallel import dp

workdir = os.environ["HVDTPU_TEST_WORKDIR"]
host_id = os.environ.get("HVDTPU_HOST_ID", "localhost")
STEPS = int(os.environ["HVDTPU_TEST_SOAK_STEPS"])
CKDIR = os.path.join(workdir, "ckpt")


def log(rec):
    with open(os.path.join(workdir, "progress.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\\n")


native.init()
hvd.init(devices=jax.devices("cpu")[:1])


def params0():
    rng = np.random.RandomState(0)
    return {
        "w": jnp.asarray(rng.randn(8, 4) * 0.5, jnp.float32),
        "b": jnp.zeros((4,), jnp.float32),
    }


def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)


def batch_for(step):
    rng = np.random.RandomState(1000 + step)
    return (
        jnp.asarray(rng.randn(16, 8), jnp.float32),
        jnp.asarray(rng.randn(16, 4), jnp.float32),
    )


cfg = GuardConfig(max_skips=4, warmup=2, audit_every=1)
step_fn, opt = dp.make_train_step(
    loss_fn, optax.sgd(0.05), guard=cfg, donate=False,
)
box = {"ts": dp.init_state(params0(), opt, guard=True)}
state = elastic.ObjectState(step=0)
try:
    box["ts"] = ckptlib.restore_checkpoint(CKDIR, box["ts"])
    state.step = int(box["ts"].step)
    state.save()
    log({"host": host_id, "resumed_at": state.step})
except FileNotFoundError:
    pass


@elastic.run
def train(st):
    while st.step < STEPS:
        attempt = int(box["ts"].step) + 1
        ts, loss = step_fn(box["ts"], batch_for(int(box["ts"].step)))
        box["ts"] = ts
        lossf = float(loss)
        rec = {
            "host": host_id,
            "rank": native.rank(),
            "size": native.size(),
            "attempt": attempt,
            "step": int(ts.step),
            "skipped_total": int(ts.guard.skipped),
            "loss": lossf if np.isfinite(lossf) else None,
        }
        rt = step_fn.guard_runtime
        if rt.last_report is not None and rt.last_report.step == int(ts.step):
            rec["audit"] = rt.last_report.as_record()
            rt.last_report = None
        committed = int(ts.step) > st.step
        st.step = int(ts.step)
        if committed and native.rank() == 0:
            # Post-audit save: a step only reaches disk after the
            # cross-replica checksum round said this rank is clean.
            ckptlib.save_checkpoint(
                CKDIR, ts, step=st.step, keep=STEPS + 1, force=True
            )
        log(rec)
        st.commit()
    return st.step


train(state)
final = jax.device_get(box["ts"])
log({
    "host": host_id,
    "rank": native.rank(),
    "final_step": int(final.step),
    "final_w": [float(x) for x in np.asarray(final.params["w"]).reshape(-1)],
    "skipped_total": int(final.guard.skipped),
})
native.shutdown()
'''

SILENT_VICTIM = "127.0.0.2"  # rank 1 of the sorted 3-host world


# Elastic inference-serving worker (the `serve` scenario): joins the
# elastic world exactly like a training worker (rendezvous, heartbeat
# lease), then serves leased request batches over the KV plane
# (horovod_tpu.serve.kv) with a jit inference step until the coordinator
# publishes shutdown. The chaos `serve.dispatch:crash` site hard-kills
# one incarnation mid-batch; the invariant machinery asserts the
# coordinator re-queued its in-flight requests and every request was
# answered exactly once with the exact fault-free values.
SERVE_WORKER = '''
import json, os, sys, time
import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax

from horovod_tpu import checkpoint as ckptlib
from horovod_tpu.elastic import worker as ew
from horovod_tpu.serve import kv as skv

workdir = os.environ["HVDTPU_TEST_WORKDIR"]
host_id = os.environ["HVDTPU_HOST_ID"]


def log(rec):
    with open(os.path.join(workdir, "progress.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\\n")


rank, size = ew.join_world()
# Manifest-verified weight load (CRC walk-back on corruption): every
# serving worker restores its own copy, exactly like one host's replica.
state, ckpt_step, _ = ckptlib.hot_swap_restore(
    os.path.join(workdir, "ckpt"),
    {"scale": np.float32(0), "bias": np.float32(0)},
)
scale, bias = float(state["scale"]), float(state["bias"])
log({"host": host_id, "serve_joined": rank, "size": size,
     "ckpt_step": ckpt_step,
     "spawn": int(os.environ.get("HVDTPU_SPAWN_ROUND", "0"))})
infer = jax.jit(lambda b: b * scale + bias)
served = skv.kv_worker_serve_loop(
    infer,
    client=ew._kv_client(),
    host_id=host_id,
    poll_secs=0.05,
    on_batch=lambda rec: log(dict(rec, kind="serve_batch")),
)
log({"host": host_id, "serve_done": served})
ew.heartbeat_stop()
sys.exit(0)
'''

SERVE_REQUESTS = 32


def run_serve_scenario(name: str = "serve", requests: int = SERVE_REQUESTS,
                       workdir: Optional[str] = None,
                       timeout: float = 180.0, seed: int = 0) -> dict:
    """The serving chaos scenario: a 2-host elastic serving pool under
    closed-loop load, one worker hard-killed mid-flight (``serve`` — the
    fault-free twin is ``serve_baseline``). Returns a result dict for
    :func:`check_invariants`."""
    import numpy as np
    from unittest import mock

    from horovod_tpu.runner import elastic_driver as ed
    from horovod_tpu.serve import kv as skv
    from horovod_tpu.serve.dispatcher import Dispatcher

    workdir = workdir or tempfile.mkdtemp(prefix=f"chaos_{name}_")
    with open(os.path.join(workdir, "hosts.txt"), "w") as f:
        f.write("localhost:1\n127.0.0.1:1\n")
    disco = os.path.join(workdir, "discover.sh")
    with open(disco, "w") as f:
        f.write(f"#!/bin/sh\ncat {workdir}/hosts.txt\n")
    os.chmod(disco, os.stat(disco).st_mode | stat.S_IEXEC)
    worker_py = os.path.join(workdir, "worker.py")
    with open(worker_py, "w") as f:
        f.write(SERVE_WORKER)
    # The weights the pool serves (x -> 2x + 1), manifest-verified at
    # every worker's load.
    from horovod_tpu import checkpoint as ckptlib

    ckptlib.save_checkpoint(
        os.path.join(workdir, "ckpt"),
        {"scale": np.float32(2.0), "bias": np.float32(1.0)},
        step=1, force=True,
    )

    env = {
        "HVDTPU_TEST_WORKDIR": workdir,
        "HVDTPU_ELASTIC_POLL_SECS": "0.1",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
        "JAX_PLATFORMS": "cpu",
        # The killed host must come back: probation re-admits it.
        "HVDTPU_BLACKLIST_COOLDOWN": "1.0",
    }
    if name == "serve":
        # Hard-kill 127.0.0.1's FIRST incarnation at its 2nd leased
        # batch — mid-flight by construction (its other lease and the
        # half-served batch are outstanding when it dies).
        env["HVDTPU_CHAOS"] = (
            "serve.dispatch:crash@step=2;host=127.0.0.1;spawn=0"
        )
        env["HVDTPU_CHAOS_SEED"] = str(seed)
    trace_dir = _arm_trace(workdir, env)

    with mock.patch.dict(os.environ, {"HVDTPU_BLACKLIST_COOLDOWN": "1.0"}):
        # The blacklist cooldown is read at HostManager construction:
        # the killed host must be re-admitted on probation.
        driver = ed.ElasticDriver(ed.HostDiscoveryScript(disco), min_np=1)
    job = ed.ElasticJob(
        [sys.executable, worker_py],
        driver,
        extra_env=env,
        verbose=True,
        output_dir=os.path.join(workdir, "logs"),
        drain_timeout=30.0,
    )
    result: dict = {}

    def _run():
        try:
            with mock.patch.dict(
                os.environ, {"HVDTPU_BLACKLIST_COOLDOWN": "1.0"}
            ), mock.patch.object(ed, "DISCOVER_HOSTS_FREQUENCY_SECS", 0.1):
                result["rc"] = job.run()
        except BaseException as exc:
            result["exc"] = repr(exc)

    t = threading.Thread(target=_run, daemon=True)
    t.start()

    answered: Dict[int, list] = {}
    errors: Dict[int, str] = {}
    dispatcher = Dispatcher(
        batch_size=4, batch_timeout_ms=30.0,
        request_timeout_secs=2.0, max_attempts=10,
    )
    coord = None
    try:
        # The KV server starts inside job.run(); wait for it.
        t0 = time.time()
        while getattr(job.server, "_server", None) is None:
            if time.time() - t0 > 30 or not t.is_alive():
                raise RuntimeError("rendezvous server never started")
            time.sleep(0.05)
        coord = skv.KVServeCoordinator(job.server, dispatcher,
                                       poll_secs=0.05).start()
        t0 = time.time()
        while not coord.ready_workers():
            if time.time() - t0 > 60:
                raise RuntimeError("no serving worker became ready")
            time.sleep(0.05)
        futs = {}
        for i in range(requests):
            futs[i] = dispatcher.submit(
                np.full(3, float(i), np.float32)
            )
            # A front-loaded burst keeps both workers holding leases
            # (the crash lands mid-flight), then a trickle sustains
            # traffic across the blacklist/respawn window.
            time.sleep(0.0 if i < requests // 2 else 0.05)
        deadline = time.time() + timeout
        for i, f in futs.items():
            try:
                f.result(timeout=max(1.0, deadline - time.time()))
                answered[i] = list(np.asarray(f.result(0)).tolist())
            except Exception as e:  # noqa: BLE001 - recorded as evidence
                errors[i] = repr(e)
    except Exception as exc:  # noqa: BLE001
        result.setdefault("exc", repr(exc))
    finally:
        if coord is not None:
            coord.stop(shutdown_workers=True)
        else:
            try:
                job.server.put("serve_ctl", "shutdown", b"1")
            except Exception:
                pass
    t.join(timeout=60.0)
    diagnostics = None
    timed_out = t.is_alive()  # verdict BEFORE teardown may unstick it
    if timed_out:
        # Same hard-deadline contract as the training scenarios: dump
        # evidence and demolish the wedged job rather than hanging.
        diagnostics = _timeout_diagnostics(workdir, job)
        _teardown_job(job)
        t.join(timeout=10.0)
        _attach_flight_recorder(diagnostics, workdir)
        print(
            f"chaos_soak: serve scenario {name!r} wedged past its "
            f"deadline; diagnostics:\n{json.dumps(diagnostics, indent=1)}",
            file=sys.stderr, flush=True,
        )
    _disarm_trace()

    records: List[dict] = []
    progress = os.path.join(workdir, "progress.jsonl")
    if os.path.exists(progress):
        with open(progress) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    pass
    return {
        "scenario": name,
        "workdir": workdir,
        "trace_dir": trace_dir,
        "diagnostics": diagnostics,
        "timed_out": timed_out,
        "rc": result.get("rc"),
        "exc": result.get("exc"),
        "records": records,
        "quarantined": [],
        "requests": requests,
        "answered": answered,
        "errors": errors,
        "requeued": dispatcher.n_requeued,
        "baseline": (
            run_serve_scenario(
                "serve_baseline", requests=requests, timeout=timeout,
                seed=seed,
            )
            if name == "serve"
            else None
        ),
    }


DECODE_STREAMS = 8
DECODE_MAX_NEW = 24


def run_decode_scenario(name: str = "decode", streams: int = DECODE_STREAMS,
                        workdir: Optional[str] = None,
                        timeout: float = 120.0, seed: int = 0) -> dict:
    """The token-level serving chaos scenario: an in-process
    :class:`~horovod_tpu.serve.engine.DecodeEngine` (2 decode workers,
    paged KV pools) under closed-loop streaming load, one worker killed
    by ``serve.decode:crash`` MID-SEQUENCE (``decode`` — the fault-free
    twin is ``decode_baseline``). The invariants: rc=0, every stream
    completes exactly once, finals token-identical to the fault-free
    run (killed streams resume from prompt + committed tokens on the
    survivor), and ``n_requeued > 0`` proves the kill landed mid-stream.
    """
    from horovod_tpu import chaos as chaos_mod
    from horovod_tpu.serve import CacheLM, CacheLMConfig, DecodeEngine

    workdir = workdir or tempfile.mkdtemp(prefix=f"chaos_{name}_")
    trace_dir = _arm_trace(workdir, {})
    cfg = CacheLMConfig(
        vocab=32, n_layers=2, n_heads=2, head_dim=8, max_positions=256
    )
    model = CacheLM(cfg, block_size=8)
    params = model.init_params(seed)
    chaos_mod._reset_for_tests()
    if name == "decode":
        # Kill whichever decode worker reaches its 4th round first — by
        # then both workers hold mid-flight streams (8 streams over 2x2
        # decode rows), so the crash lands mid-sequence by construction.
        chaos_mod.plan("serve.decode:crash@step=4;n=1", seed=seed)
    eng = DecodeEngine(
        model, params, workers=2, rows=2, kv_blocks=32, kv_block_size=8,
        max_seq_len=64,
    )
    result: dict = {}
    answered: Dict[int, list] = {}
    errors: Dict[int, str] = {}

    def _run():
        try:
            eng.start()
            futs = {}
            for i in range(streams):
                futs[i] = eng.submit(
                    [1 + (i % 5), 2, (3 * i) % 7], DECODE_MAX_NEW
                )
                # Burst half, then trickle: every row holds a stream
                # when the crash fires, and traffic spans the recovery.
                time.sleep(0.0 if i < streams // 2 else 0.01)
            deadline = time.time() + timeout
            for i, f in futs.items():
                try:
                    answered[i] = list(
                        f.result(timeout=max(1.0, deadline - time.time()))
                    )
                except Exception as e:  # noqa: BLE001 - evidence
                    errors[i] = repr(e)
            result["rc"] = 0
        except BaseException as exc:
            result["exc"] = repr(exc)

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    t.join(timeout=timeout + 30.0)
    diagnostics = None
    timed_out = t.is_alive()  # verdict BEFORE teardown may unstick it
    workers_left = eng.n_workers  # before stop() drains the survivors
    if timed_out:
        diagnostics = _timeout_diagnostics(workdir)
        eng.stop(drain=False)
        t.join(timeout=10.0)
        _attach_flight_recorder(diagnostics, workdir)
        print(
            f"chaos_soak: decode scenario {name!r} wedged past its "
            f"deadline; diagnostics:\n{json.dumps(diagnostics, indent=1)}",
            file=sys.stderr, flush=True,
        )
    else:
        eng.stop()
    chaos_mod._reset_for_tests()
    _disarm_trace()
    return {
        "scenario": name,
        "workdir": workdir,
        "trace_dir": trace_dir,
        "diagnostics": diagnostics,
        "timed_out": timed_out,
        "rc": result.get("rc"),
        "exc": result.get("exc"),
        "records": [],
        "quarantined": [],
        "streams": streams,
        "answered": answered,
        "errors": errors,
        "requeued": eng.n_requeued,
        "finished": eng.n_finished,
        "workers_left": workers_left,
        "baseline": (
            run_decode_scenario(
                "decode_baseline", streams=streams, timeout=timeout,
                seed=seed,
            )
            if name == "decode"
            else None
        ),
    }


def check_decode_invariants(res: dict) -> List[str]:
    """Violated invariants for one decode scenario result ([] = ok)."""
    name = res["scenario"]
    problems: List[str] = []
    if res["timed_out"]:
        return [f"{name}: streams did not finish in time"]
    if res.get("exc"):
        return [f"{name}: harness raised {res['exc']}"]
    if res["rc"] != 0:
        problems.append(f"{name}: rc={res['rc']}, wanted 0")
    n = res["streams"]
    # ZERO dropped streams: every submission resolves exactly once
    # (futures settle once by construction; the count must be exact).
    if res["errors"]:
        problems.append(
            f"{name}: {len(res['errors'])} stream(s) failed: "
            f"{dict(list(res['errors'].items())[:3])}"
        )
    if len(res["answered"]) != n:
        problems.append(f"{name}: {len(res['answered'])}/{n} streams answered")
    for i, toks in res["answered"].items():
        if len(toks) != DECODE_MAX_NEW:
            problems.append(
                f"{name}: stream {i} got {len(toks)} tokens, wanted "
                f"{DECODE_MAX_NEW}"
            )
            break
    if name == "decode":
        base = res.get("baseline") or {}
        problems.extend(check_decode_invariants(base))
        # Token-identical finals vs the fault-free twin: resumed
        # streams re-emit NOTHING and lose NOTHING.
        if base and res["answered"] != base.get("answered"):
            diff = [
                i for i in res["answered"]
                if res["answered"].get(i) != base.get("answered", {}).get(i)
            ]
            problems.append(
                f"decode: streams {diff[:4]} are not token-identical to "
                "the fault-free baseline"
            )
        if res["requeued"] == 0:
            problems.append(
                "decode: nothing was re-queued — the kill did not land "
                "mid-stream"
            )
        if res.get("workers_left") != 1:
            problems.append(
                f"decode: {res.get('workers_left')} workers left, wanted "
                "exactly the 1 survivor"
            )
    return problems


# Weight-stream trainer (the `stream` scenario): an elastic worker whose
# "training" is analytic — the params at step S are a pure function of
# (seed, S) — so every incarnation of the publisher host produces
# bit-identical versions, and the decode finals against the streamed
# step-S weights are comparable token-for-token across the chaos run and
# its fault-free twin. ONE host publishes (the victim), every step,
# through the journaled rendezvous KV; rank 0 checkpoints the step so a
# respawned victim resumes (and republishes under its bumped epoch).
STREAM_WORKER = '''
import json, os, sys, time
import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax

import horovod_tpu.native as native
from horovod_tpu import elastic
from horovod_tpu import checkpoint as ckptlib
from horovod_tpu.serve import CacheLM, CacheLMConfig
from horovod_tpu.stream import WeightPublisher

workdir = os.environ["HVDTPU_TEST_WORKDIR"]
host_id = os.environ["HVDTPU_HOST_ID"]
STEPS = int(os.environ["HVDTPU_TEST_SOAK_STEPS"])
SEED = int(os.environ.get("HVDTPU_TEST_STREAM_SEED", "0"))
PUB_HOST = os.environ["HVDTPU_TEST_STREAM_PUB_HOST"]
CKDIR = os.path.join(workdir, "state_ckpt")


def log(rec):
    with open(os.path.join(workdir, "progress.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\\n")


_base = CacheLM(
    CacheLMConfig(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                  max_positions=256),
    block_size=8,
).init_params(SEED)


def params_at(step):
    # Analytic "training": identical bytes from any incarnation.
    return jax.tree.map(
        lambda x: (np.asarray(x) + np.float32(0.001) * step).astype(
            np.asarray(x).dtype
        ),
        _base,
    )


native.init()
pub = WeightPublisher(publish_every=1) if host_id == PUB_HOST else None
state = elastic.ObjectState(step=0)
try:
    restored = ckptlib.restore_checkpoint(CKDIR, {"step": np.int64(0)})
    state.step = int(restored["step"])
    state.save()
    log({"host": host_id, "resumed_at": state.step})
except FileNotFoundError:
    pass


@elastic.run
def train(st):
    while st.step < STEPS:
        native.allreduce(np.full(2, 0.5, np.float32), name="sync")
        st.step += 1
        if native.rank() == 0:
            ckptlib.save_checkpoint(
                CKDIR, {"step": np.int64(st.step)},
                step=st.step, keep=STEPS + 1,
            )
        if pub is not None:
            pub.maybe_publish(params_at(st.step), st.step)
            log({"host": host_id, "step": st.step, "epoch": pub.epoch,
                 "published": pub.n_published,
                 "spawn": int(os.environ.get("HVDTPU_SPAWN_ROUND", "0"))})
        st.commit()
    return st.step


train(state)
if pub is not None:
    pub.flush()
    log({"host": host_id, "publisher_done": state.step,
         "published": pub.n_published, "torn": pub.n_torn_injected})
log({"host": host_id, "final_step": state.step})
native.shutdown()
'''

STREAM_VICTIM = "127.0.0.1"  # the publisher host the chaos kills
STREAM_DECODE_STREAMS = 8


class _MemKV:
    """Post-job stand-in for the driver's KV (the real server dies with
    the job): holds whatever the harness injects — e.g. the stale-epoch
    manifest a dead trainer's late write would have left."""

    def __init__(self):
        self._store: Dict[str, Dict[str, bytes]] = {}

    def put(self, scope: str, key: str, value: bytes) -> None:
        self._store.setdefault(scope, {})[key] = value

    def scope_items(self, scope: str) -> Dict[str, bytes]:
        return dict(self._store.get(scope, {}))


def _stream_params(seed: int, step: int):
    """The harness-side twin of the worker's analytic params (same
    formula, bit-identical)."""
    import jax
    import numpy as np

    from horovod_tpu.serve import CacheLM, CacheLMConfig

    base = CacheLM(
        CacheLMConfig(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                      max_positions=256),
        block_size=8,
    ).init_params(seed)
    return jax.tree.map(
        lambda x: (np.asarray(x) + np.float32(0.001) * step).astype(
            np.asarray(x).dtype
        ),
        base,
    )


def run_stream_scenario(name: str = "stream", steps: int = DEFAULT_STEPS,
                        workdir: Optional[str] = None,
                        timeout: float = 240.0, seed: int = 0) -> dict:
    """The live-weight-streaming chaos scenario (``stream``; fault-free
    twin ``stream_baseline``): an elastic trainer streams per-step
    weight versions through the journaled KV into an in-process
    :class:`~horovod_tpu.serve.engine.DecodeEngine` via
    :class:`~horovod_tpu.stream.StreamSubscriber`, while the fault plan
    kills the publisher host mid-run, tears one publish on the wire
    (``publish.delta:torn`` — the wire image of a trainer dying
    mid-publish), and kills + adopts the driver. Post-job the harness
    injects a stale-epoch manifest (the late write of a dead trainer)
    and then starves the stream into the CheckpointWatcher fallback.
    :func:`check_stream_invariants` audits: zero torn applies, stale
    epoch rejected, fallback proven, decode finals token-identical to
    the twin."""
    import numpy as np  # noqa: F401 - worker-side twin below
    from unittest import mock

    from horovod_tpu import chaos as _chaos
    from horovod_tpu import checkpoint as ckptlib
    from horovod_tpu.runner import elastic_driver as ed
    from horovod_tpu.serve import CacheLM, CacheLMConfig, DecodeEngine
    from horovod_tpu.stream import StreamSubscriber
    from horovod_tpu.stream import protocol as _sproto

    # The victim must respawn, resume and publish AFTER the driver
    # adoption for the epoch/torn legs to fire — floor the step count so
    # pacing x steps outlasts blacklist cooldown + adoption with margin.
    steps = max(steps, 10)
    workdir = workdir or tempfile.mkdtemp(prefix=f"chaos_{name}_")
    journal_dir = os.path.join(workdir, "journal")
    serve_ckpt = os.path.join(workdir, "serve_ckpt")
    with open(os.path.join(workdir, "hosts.txt"), "w") as f:
        f.write(f"localhost:1\n{STREAM_VICTIM}:1\n")
    disco = os.path.join(workdir, "discover.sh")
    with open(disco, "w") as f:
        f.write(f"#!/bin/sh\ncat {workdir}/hosts.txt\n")
    os.chmod(disco, os.stat(disco).st_mode | stat.S_IEXEC)
    worker_py = os.path.join(workdir, "worker.py")
    with open(worker_py, "w") as f:
        f.write(STREAM_WORKER)

    driver_env = {
        "HVDTPU_BLACKLIST_COOLDOWN": "1.0",
        "HVT_DATA_TIMEOUT_SECS": "10",
    }
    env = {
        "HVDTPU_TEST_WORKDIR": workdir,
        "HVDTPU_TEST_SOAK_STEPS": str(steps),
        "HVDTPU_TEST_STREAM_SEED": str(seed),
        "HVDTPU_TEST_STREAM_PUB_HOST": STREAM_VICTIM,
        "HVDTPU_ELASTIC_POLL_SECS": "0.1",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
        "JAX_PLATFORMS": "cpu",
    }
    if name == "stream":
        # Rule ORDER matters (first-match-wins): the conditioned crash
        # precedes the every-commit pacing slow. The torn publish fires
        # on the RESPAWNED victim's first publish past step 7 — after
        # the adoption, on the epoch-bumped publisher.
        env["HVDTPU_CHAOS"] = (
            f"publish.delta:torn@step=2;n=1;host={STREAM_VICTIM};spawn=0,"
            f"publish.delta:torn@after=7;n=1;host={STREAM_VICTIM},"
            f"worker.step:crash@step=2;host={STREAM_VICTIM};spawn=0,"
            "worker.step:slow=0.3"
        )
    else:
        env["HVDTPU_CHAOS"] = "worker.step:slow=0.3"  # pacing parity only
    env["HVDTPU_CHAOS_SEED"] = str(seed)
    env.update(driver_env)
    _arm_trace(workdir, env)

    # The serving side, in-process: engine starts on the step-0 analytic
    # params; the subscriber follows whatever KV server the CURRENT job
    # incarnation owns (the callable is re-evaluated every poll, so the
    # adoption handoff is followed automatically).
    model = CacheLM(
        CacheLMConfig(vocab=32, n_layers=2, n_heads=2, head_dim=8,
                      max_positions=256),
        block_size=8,
    )
    base_params = model.init_params(seed)
    eng = DecodeEngine(
        model, base_params, workers=2, rows=2, kv_blocks=32,
        kv_block_size=8, max_seq_len=64,
    )
    eng.start()
    job_ref: dict = {}
    kv_override: dict = {}

    def _kv():
        if "kv" in kv_override:
            return kv_override["kv"]
        job = job_ref.get("job")
        return getattr(job, "server", None) if job is not None else None

    sub = StreamSubscriber(
        eng, kv=_kv, poll_secs=0.05,
        staleness_secs=1e9,  # the fallback leg arms this later
        ckpt_dir=serve_ckpt,
    )
    eng.attach_stream(sub)
    sub.start()

    # Mirror the live ``stream`` scope into the post-job stand-in KV so
    # the server's death with the job can't strand the final version on
    # the wire (the snapshot is atomic under the store lock, so the
    # write-head-last ordering survives the copy).
    mem_kv = _MemKV()
    mirror_stop = threading.Event()

    def _mirror():
        while not mirror_stop.is_set():
            server = _kv()
            if server is not None and hasattr(server, "scope_items"):
                try:
                    for k, v in server.scope_items("stream").items():
                        mem_kv.put("stream", k, v)
                except Exception:  # noqa: BLE001 - server may be mid-death
                    pass
            mirror_stop.wait(0.05)

    mirror_t = threading.Thread(target=_mirror, daemon=True)
    mirror_t.start()

    result: dict = {}
    deadline = time.time() + timeout

    def _run(adopt: bool, key: str):
        try:
            with mock.patch.dict(os.environ, driver_env), mock.patch.object(
                ed, "DISCOVER_HOSTS_FREQUENCY_SECS", 0.1
            ):
                result[key] = ed.run_elastic(
                    [sys.executable, worker_py],
                    discovery_script=disco,
                    min_np=1,
                    reset_limit=10,
                    extra_env=env,
                    verbose=True,
                    output_dir=os.path.join(workdir, "logs"),
                    drain_timeout=30.0,
                    job_ref=job_ref,
                    journal_dir=journal_dir,
                    adopt=adopt,
                )
        except BaseException as exc:
            result[f"{key}_exc"] = repr(exc)

    adopted_hosts: List[str] = []
    if name == "stream":
        # Phase 0/1: original driver, armed to die in round 2 — the
        # round that respawns the struck publisher host.
        _chaos.plan("driver.crash:crash@step=2;n=1", seed=seed)
        t1 = threading.Thread(target=_run, args=(False, "rc1"), daemon=True)
        t1.start()
        t1.join(timeout=max(5.0, deadline - time.time()))
        _chaos.clear()
        timed_out = t1.is_alive()
        if timed_out:
            _teardown_job(job_ref.get("job"))
            t1.join(timeout=10.0)
        else:
            # Phase 2: adopt the journaled state and the orphaned
            # workers; the subscriber's kv callable follows the switch.
            job_ref.clear()
            t2 = threading.Thread(
                target=_run, args=(True, "rc"), daemon=True
            )
            t2.start()
            t2.join(timeout=max(5.0, deadline - time.time()))
            timed_out = t2.is_alive()
            if timed_out:
                _teardown_job(job_ref.get("job"))
                t2.join(timeout=10.0)
            job2 = job_ref.get("job")
            if job2 is not None:
                adopted_hosts = list(job2.adopted_hosts)
    else:
        t1 = threading.Thread(target=_run, args=(False, "rc"), daemon=True)
        t1.start()
        t1.join(timeout=max(5.0, deadline - time.time()))
        timed_out = t1.is_alive()
        if timed_out:
            _teardown_job(job_ref.get("job"))
            t1.join(timeout=10.0)

    # The job's KV server died with the job; park the subscriber on the
    # mirrored stand-in (same final scope, stream now quiet) so the
    # post-mortem legs below can inject exactly what a dead trainer's
    # late write would have left behind.
    mirror_stop.set()
    mirror_t.join(timeout=5.0)
    kv_override["kv"] = mem_kv

    # The final published version must land on the fleet: the head is
    # written strictly last and nothing overwrites it after the job, so
    # this converges unless delivery is actually broken.
    final_version = None
    if not timed_out:
        t0 = time.time()
        while time.time() - t0 < 30.0:
            with sub._lock:
                final_version = sub._last_version
            if final_version == steps:
                break
            time.sleep(0.05)

    # Decode finals on the streamed step-N weights (token-identity vs
    # the fault-free twin is the headline invariant).
    answered: Dict[int, list] = {}
    errors: Dict[int, str] = {}
    if not timed_out and final_version == steps:
        futs = {}
        for i in range(STREAM_DECODE_STREAMS):
            futs[i] = eng.submit(
                [1 + (i % 5), 2, (3 * i) % 7], DECODE_MAX_NEW
            )
        for i, f in futs.items():
            try:
                answered[i] = list(f.result(timeout=60.0))
            except Exception as e:  # noqa: BLE001 - evidence
                errors[i] = repr(e)

    if name == "stream" and not timed_out:
        # Late write from a dead trainer: a manifest from a lower epoch
        # than anything seen must be REJECTED (never staged, never
        # flipped), deterministically.
        stale = _sproto.frame_manifest(
            version=steps + 7, epoch=-1, step=steps + 7,
            layout={}, buckets=[],
        )
        mem_kv.put("stream", _sproto.HEAD_KEY, stale)
        t0 = time.time()
        while time.time() - t0 < 10.0:
            with sub._lock:
                if sub.n_epoch_rejected > 0:
                    break
            time.sleep(0.05)
        # Stream-stall fallback: the trainer is gone, so the stream is
        # permanently stale — arm a tight threshold and publish a NEWER
        # whole checkpoint; the subscriber must fall back to it via the
        # CheckpointWatcher path.
        ckptlib.save_checkpoint(
            serve_ckpt, _stream_params(seed, steps + 1),
            step=steps + 1, force=True,
        )
        sub.staleness_secs = 0.3
        t0 = time.time()
        while time.time() - t0 < 15.0:
            with sub._lock:
                if sub.n_fallbacks > 0:
                    break
            time.sleep(0.05)

    diagnostics = None
    if timed_out:
        diagnostics = _timeout_diagnostics(workdir, job_ref.get("job"))
        _attach_flight_recorder(diagnostics, workdir)
        print(
            f"chaos_soak: stream scenario {name!r} blew its deadline; "
            f"diagnostics:\n{json.dumps(diagnostics, indent=1)}",
            file=sys.stderr, flush=True,
        )
    _disarm_trace()

    # Evidence BEFORE teardown (stop() drains the workers away).
    with eng._cond:
        engine_version_log = list(eng.stream_version_log)
        worker_version_logs = {
            n: list(w.version_log) for n, w in eng._workers.items()
        }
    with sub._lock:
        applied_log = [list(t) for t in sub.applied_log]
        n_torn = sub.n_torn
        n_epoch_rejected = sub.n_epoch_rejected
        n_fallbacks = sub.n_fallbacks
        sub_error = sub.last_error
    eng.stop()  # stops the attached subscriber first

    records: List[dict] = []
    progress = os.path.join(workdir, "progress.jsonl")
    if os.path.exists(progress):
        with open(progress) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    pass
    return {
        "scenario": name,
        "steps": steps,
        "workdir": workdir,
        "timed_out": timed_out,
        "rc": result.get("rc"),
        "exc": result.get("rc_exc"),
        "crash_exc": result.get("rc1_exc"),  # must name DriverCrashed
        "records": records,
        "quarantined": [],
        "diagnostics": diagnostics,
        "adopted_hosts": adopted_hosts,
        "final_version": final_version,
        "applied_log": applied_log,
        "engine_version_log": engine_version_log,
        "worker_version_logs": worker_version_logs,
        "n_torn": n_torn,
        "n_epoch_rejected": n_epoch_rejected,
        "n_fallbacks": n_fallbacks,
        "sub_error": sub_error,
        "answered": answered,
        "errors": errors,
        "baseline": (
            run_stream_scenario(
                "stream_baseline", steps=steps, timeout=timeout, seed=seed
            )
            if name == "stream"
            else None
        ),
    }


def check_stream_invariants(res: dict) -> List[str]:
    """Violated invariants for one stream scenario result ([] = ok)."""
    name = res["scenario"]
    problems: List[str] = []
    if res["timed_out"]:
        return [f"{name}: job did not finish in time"]
    if res.get("exc"):
        return [f"{name}: harness raised {res['exc']}"]
    if res["rc"] != 0:
        problems.append(f"{name}: job rc={res['rc']}, wanted 0")
    steps = res["steps"]
    if res.get("final_version") != steps:
        problems.append(
            f"{name}: final applied version {res.get('final_version')}, "
            f"wanted {steps} (last error: {res.get('sub_error')})"
        )
    # The torn-set-proof core: every version the engine EVER flipped in,
    # and every version any decode worker decoded under, came through
    # the subscriber's CRC-verified all-or-nothing staging.
    applied = {int(v) for v, _ in res["applied_log"]}
    bad = [v for v in res["engine_version_log"] if v not in applied]
    if bad:
        problems.append(
            f"{name}: engine flipped versions {bad[:4]} the subscriber "
            "never verified — a torn set reached serving"
        )
    for worker, versions in res["worker_version_logs"].items():
        bad = [v for v in versions if v not in applied]
        if bad:
            problems.append(
                f"{name}: decode worker {worker} served unverified "
                f"versions {bad[:4]}"
            )
    # Within one epoch versions must strictly increase (an epoch bump
    # may legally reset the floor — the trainer resumed from its
    # restored step).
    by_epoch: Dict[int, List[int]] = {}
    last_epoch = None
    for v, e in res["applied_log"]:
        by_epoch.setdefault(int(e), []).append(int(v))
        if last_epoch is not None and e < last_epoch:
            problems.append(
                f"{name}: applied epoch regressed {last_epoch} -> {e}"
            )
        last_epoch = e
    for e, versions in by_epoch.items():
        if versions != sorted(set(versions)):
            problems.append(
                f"{name}: versions within epoch {e} not strictly "
                f"increasing: {versions}"
            )
    if res["errors"]:
        problems.append(
            f"{name}: {len(res['errors'])} decode stream(s) failed: "
            f"{dict(list(res['errors'].items())[:3])}"
        )
    if len(res["answered"]) != STREAM_DECODE_STREAMS:
        problems.append(
            f"{name}: {len(res['answered'])}/{STREAM_DECODE_STREAMS} "
            "decode streams answered"
        )
    if name == "stream":
        base = res.get("baseline") or {}
        problems.extend(check_stream_invariants(base))
        if base and res["answered"] != base.get("answered"):
            diff = [
                i for i in res["answered"]
                if res["answered"].get(i) != base.get("answered", {}).get(i)
            ]
            problems.append(
                f"stream: decode streams {diff[:4]} are not "
                "token-identical to the fault-free baseline"
            )
        if res["n_torn"] < 1:
            problems.append(
                "stream: no torn set was ever observed — the injected "
                "mid-publish death left no wire damage to reject"
            )
        if res["n_epoch_rejected"] < 1:
            problems.append(
                "stream: the stale-epoch manifest was never rejected"
            )
        if res["n_fallbacks"] < 1:
            problems.append(
                "stream: the starved stream never fell back to the "
                "CheckpointWatcher path"
            )
        epochs = {int(e) for _, e in res["applied_log"]}
        if len(epochs) < 2:
            problems.append(
                f"stream: applied epochs {sorted(epochs)} — the respawned "
                "publisher's bumped epoch never reached the fleet"
            )
        if "DriverCrashed" not in (res.get("crash_exc") or ""):
            problems.append(
                f"stream: phase-1 driver ended with "
                f"{res.get('crash_exc')!r}, wanted DriverCrashed"
            )
        if not res["adopted_hosts"]:
            problems.append(
                "stream: the adopting driver re-attached no workers"
            )
    return problems


def check_serve_invariants(res: dict) -> List[str]:
    """Violated invariants for one serve scenario result ([] = ok)."""
    name = res["scenario"]
    problems: List[str] = []
    if res["timed_out"]:
        return [f"{name}: job did not finish in time"]
    if res.get("exc"):
        return [f"{name}: harness raised {res['exc']}"]
    if res["rc"] != 0:
        problems.append(f"{name}: job rc={res['rc']}, wanted 0")
    n = res["requests"]
    # ZERO dropped requests: every submission answered exactly once
    # (the future resolves once by construction; count must be exact).
    if res["errors"]:
        problems.append(
            f"{name}: {len(res['errors'])} request(s) failed/dropped: "
            f"{dict(list(res['errors'].items())[:3])}"
        )
    if len(res["answered"]) != n:
        problems.append(
            f"{name}: {len(res['answered'])}/{n} requests answered"
        )
    # Every worker loaded the manifest-verified step-1 weights.
    joined = [r for r in res["records"] if "serve_joined" in r]
    if not joined:
        problems.append(f"{name}: no serving worker ever joined")
    elif any(r.get("ckpt_step") != 1 for r in joined):
        problems.append(
            f"{name}: a worker served without the manifest-verified "
            "step-1 checkpoint"
        )
    # Exact response values: infer is x -> 2x+1 on a constant vector.
    for i, v in res["answered"].items():
        want = 2.0 * i + 1.0
        if any(abs(x - want) > 1e-6 for x in v):
            problems.append(f"{name}: request {i} answered {v}, wanted {want}")
            break
    if name == "serve":
        base = res.get("baseline") or {}
        problems.extend(check_serve_invariants(base))
        # Response-count parity with the fault-free run.
        if base and len(res["answered"]) != len(base.get("answered", {})):
            problems.append(
                f"serve: answered {len(res['answered'])} vs fault-free "
                f"{len(base.get('answered', {}))}"
            )
        # The kill really disrupted in-flight work (not a lucky miss):
        # the coordinator re-queued something, and 127.0.0.1's first
        # incarnation died after serving at least one batch.
        if res["requeued"] == 0:
            problems.append(
                "serve: nothing was re-queued — the crash did not land "
                "mid-flight"
            )
        spawns = {
            r["spawn"] for r in res["records"]
            if r.get("host") == "127.0.0.1" and "spawn" in r
        }
        if 0 not in spawns:
            problems.append(
                "serve: 127.0.0.1's first incarnation never joined"
            )
        victim_done = [
            r for r in res["records"]
            if r.get("host") == "127.0.0.1" and "serve_done" in r
        ]
        if not (len(spawns) > 1 or victim_done):
            problems.append(
                "serve: the killed host neither respawned nor finished "
                "cleanly — the fault path never resolved"
            )
    return problems


def _scenarios(steps: int) -> Dict[str, dict]:
    mid = max(2, steps // 2)
    return {
        "baseline": {
            "hosts": ["localhost:1", "127.0.0.1:1"],
            "chaos": None,
            "env": {},
        },
        "crash": {
            "hosts": ["localhost:1", "127.0.0.1:1"],
            "chaos": f"worker.step:crash@step={mid};host=127.0.0.1;spawn=0",
            # A dead ring peer must fail collectives fast, not in 300 s.
            "env": {"HVT_DATA_TIMEOUT_SECS": "10"},
        },
        "hang": {
            "hosts": ["localhost:1", "127.0.0.1:1"],
            "chaos": f"worker.step:hang@step={mid};host=127.0.0.1;spawn=0",
            "env": {
                "HVT_DATA_TIMEOUT_SECS": "10",
                # Tight lease so expiry (not the drain deadline) is what
                # catches the frozen worker.
                "HVDTPU_HEARTBEAT_SECS": "0.2",
                "HVDTPU_HEARTBEAT_TIMEOUT_SECS": "2.0",
            },
        },
        "kv_outage": {
            "hosts": ["localhost:1", "127.0.0.1:1"],
            # Every 3rd KV request fails at every worker: sustained ~33%
            # rendezvous failure across join, heartbeat and notification
            # polling. Retry + guarded polling must absorb all of it —
            # no restarts, no blacklists.
            "chaos": "kv.request:drop@every=3;n=60",
            "env": {},
        },
        "ckpt": {
            "hosts": ["localhost:1"],
            # Bit-rot the newest checkpoint, then kill the (only)
            # worker at the same step: the restart must fall back to
            # the previous intact step, and blacklist cooldown must
            # re-admit the host at all.
            "chaos": (
                f"ckpt.write:corrupt@step={mid};spawn=0,"
                f"worker.step:crash@step={mid};spawn=0"
            ),
            "env": {"HVDTPU_BLACKLIST_COOLDOWN": "1.0"},
        },
        "straggler": {
            "hosts": ["localhost:1", "127.0.0.1:1"],
            "chaos": "worker.step:slow=0.25@host=127.0.0.1",
            "env": {},
        },
        # Preemption grace: a REAL SIGTERM eviction notice lands on one
        # worker at commit mid. Its grace handler flips preempt/<host>,
        # the driver republishes a round without it, the victim's next
        # commit takes a manifest-verified priority checkpoint and the
        # decommission path walks it out cleanly — the world SHRINKS,
        # nobody is blacklisted, the survivor loses nothing. Commits
        # are paced so the round shrink (not the victim simply
        # finishing first) is what resolves the fault.
        "preempt": {
            "hosts": ["localhost:1", "127.0.0.1:1"],
            # SIGTERM at the victim's 2nd commit, every commit paced
            # 0.3 s: the driver's shrink round must land (and the
            # victim drain out) with steps to spare — the survivor must
            # demonstrably run the tail of the job at world size 1.
            "chaos": (
                "worker.step:slow=0.3,"
                "worker.preempt:sigterm@step=2;host=127.0.0.1;spawn=0"
            ),
            "env": {"HVT_DATA_TIMEOUT_SECS": "10"},
        },
        # Control-plane KV death: the rendezvous listener is torn down
        # hard mid-run (repeatedly) and re-listened on the same port
        # from the journal replay — a fresh identity epoch each time.
        # Workers ride it out on client retries + reconnect epochs:
        # nobody restarts, nobody is blacklisted, steps march on.
        "kv_server_crash": {
            "hosts": ["localhost:1", "127.0.0.1:1"],
            "chaos": "worker.step:slow=0.1",
            "driver_chaos": "kv.server:restart@after=3;every=3;n=3",
            "journal": True,
            "env": {},
        },
        # Quantized training + EF state through a crash/restore: the
        # worker is killed mid-run and must resume from the checkpointed
        # TrainState — including the error-feedback residuals — landing
        # on bit-identical final params vs the fault-free quant baseline
        # (run_scenario("quant") runs both and check_invariants compares).
        "quant_baseline": {
            "hosts": ["localhost:1"],
            "chaos": None,
            "env": {},
            "worker": QUANT_WORKER,
        },
        "quant": {
            "hosts": ["localhost:1"],
            "chaos": f"worker.step:crash@step={mid};spawn=0",
            # Single host: the crashed host must be re-admitted from
            # blacklist probation for the respawn (same shape as ckpt).
            "env": {"HVDTPU_BLACKLIST_COOLDOWN": "1.0"},
            "worker": QUANT_WORKER,
        },
        # Fail-silent faults (see SILENT_WORKER above): three loopback
        # hosts so the checksum audit has a strict majority to vote
        # with. grad.nan hits EVERY rank at attempt 2 (batches are
        # replicated — the guard skips in lockstep and the step is
        # retried); grad.bitflip hits only the victim's params after
        # commit mid, and must be audit-detected within one window.
        "silent_baseline": {
            "hosts": ["127.0.0.1:1", "127.0.0.2:1", "127.0.0.3:1"],
            "chaos": None,
            "env": {},
            "worker": SILENT_WORKER,
        },
        "silent": {
            "hosts": ["127.0.0.1:1", "127.0.0.2:1", "127.0.0.3:1"],
            "chaos": (
                "grad.nan:nan@step=2;n=1,"
                f"grad.bitflip:bitflip@step={mid};host={SILENT_VICTIM};n=1"
            ),
            "env": {},
            "worker": SILENT_WORKER,
        },
    }


SCENARIO_NAMES = [
    n for n in _scenarios(DEFAULT_STEPS) if not n.endswith("baseline")
] + ["serve", "decode", "stream", "driver_crash", "autotune"]


def run_scenario(name: str, steps: int = DEFAULT_STEPS,
                 workdir: Optional[str] = None,
                 timeout: float = 180.0, seed: int = 0) -> dict:
    """Run one scenario; returns a result dict (no assertions — the
    caller checks invariants via :func:`check_invariants`)."""
    from unittest import mock

    from horovod_tpu.runner import elastic_driver as ed

    if name in ("serve", "serve_baseline"):
        return run_serve_scenario(
            name, workdir=workdir, timeout=timeout, seed=seed
        )
    if name in ("decode", "decode_baseline"):
        return run_decode_scenario(
            name, workdir=workdir, timeout=timeout, seed=seed
        )
    if name in ("stream", "stream_baseline"):
        return run_stream_scenario(
            name, steps=steps, workdir=workdir,
            timeout=max(timeout, 240.0), seed=seed,
        )
    if name == "driver_crash":
        return run_driver_crash_scenario(
            steps=steps, workdir=workdir, timeout=timeout, seed=seed
        )
    if name == "autotune":
        return run_autotune_scenario(
            workdir=workdir, timeout=max(timeout, 240.0), seed=seed
        )
    spec = _scenarios(steps).get(name)
    if spec is None:
        raise ValueError(
            f"unknown scenario {name!r} (choose from "
            f"{', '.join(['baseline'] + SCENARIO_NAMES)})"
        )
    workdir = workdir or tempfile.mkdtemp(prefix=f"chaos_{name}_")
    with open(os.path.join(workdir, "hosts.txt"), "w") as f:
        f.write("\n".join(spec["hosts"]) + "\n")
    disco = os.path.join(workdir, "discover.sh")
    with open(disco, "w") as f:
        f.write(f"#!/bin/sh\ncat {workdir}/hosts.txt\n")
    os.chmod(disco, os.stat(disco).st_mode | stat.S_IEXEC)
    worker_py = os.path.join(workdir, "worker.py")
    with open(worker_py, "w") as f:
        f.write(spec.get("worker") or WORKER)

    env = {
        "HVDTPU_TEST_WORKDIR": workdir,
        "HVDTPU_TEST_SOAK_STEPS": str(steps),
        "HVDTPU_ELASTIC_POLL_SECS": "0.1",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
        "JAX_PLATFORMS": "cpu",
    }
    env.update(spec["env"])
    if spec["chaos"]:
        env["HVDTPU_CHAOS"] = spec["chaos"]
        env["HVDTPU_CHAOS_SEED"] = str(seed)
    trace_dir = _arm_trace(workdir, env)

    result: dict = {}
    job_ref: dict = {}
    journal_dir = (
        os.path.join(workdir, "journal") if spec.get("journal") else None
    )
    # Control-plane fault scenarios arm a DRIVER-side schedule too (the
    # kv.server / driver.crash sites live in the in-process run loop);
    # ordinary scenarios keep the chaos worker-only — there the driver
    # is the recovery authority, not a fault target.
    if spec.get("driver_chaos"):
        from horovod_tpu import chaos as _chaos

        _chaos.plan(spec["driver_chaos"], seed=seed)

    # Arm the in-process DRIVER's goodput ledger: fault scenarios must
    # prove their lost wall-clock lands in the right attribution
    # category (crash/hang → rescale_downtime), not just that the job
    # recovers. Workers are subprocesses and stay unarmed — the
    # assertions are driver-side.
    from horovod_tpu.obs import goodput as _goodput

    _goodput._reset_for_tests()
    _goodput.enable()

    def _run():
        try:
            # Scenario env reaches the in-process DRIVER too (heartbeat
            # timeout, blacklist cooldown are driver-side knobs).
            with mock.patch.dict(os.environ, spec["env"]), mock.patch.object(
                ed, "DISCOVER_HOSTS_FREQUENCY_SECS", 0.1
            ):
                result["rc"] = ed.run_elastic(
                    [sys.executable, worker_py],
                    discovery_script=disco,
                    min_np=1,
                    reset_limit=10,
                    extra_env=env,
                    verbose=True,
                    output_dir=os.path.join(workdir, "logs"),
                    drain_timeout=30.0,
                    job_ref=job_ref,
                    journal_dir=journal_dir,
                )
        except BaseException as exc:
            result["exc"] = repr(exc)

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    t.join(timeout=timeout)
    if spec.get("driver_chaos"):
        from horovod_tpu import chaos as _chaos

        _chaos.clear()
    diagnostics = None
    # Deadline verdict is taken HERE, before the teardown below may
    # unstick the thread — a demolished run must still report as timed
    # out, not masquerade as a finish.
    timed_out = t.is_alive()
    if timed_out:
        # Hard per-scenario deadline: dump evidence (log tails + the KV
        # plane's last published round state), then tear the wedged job
        # down so one stuck scenario can't hang the whole soak.
        diagnostics = _timeout_diagnostics(workdir, job_ref.get("job"))
        _teardown_job(job_ref.get("job"))
        t.join(timeout=10.0)
        # AFTER teardown: the kill SIGTERMs are what make the wedged
        # workers write their flight-recorder dumps — merge them into
        # the evidence bundle so every blown deadline ships a "who was
        # where" timeline, not just log tails.
        _attach_flight_recorder(diagnostics, workdir)
        print(
            f"chaos_soak: scenario {name!r} blew its {timeout:.0f}s "
            f"deadline; diagnostics:\n{json.dumps(diagnostics, indent=1)}",
            file=sys.stderr, flush=True,
        )
    _disarm_trace()

    records: List[dict] = []
    progress = os.path.join(workdir, "progress.jsonl")
    if os.path.exists(progress):
        with open(progress) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    pass  # a crash can tear the final line
    ckdir = os.path.join(workdir, "ckpt")
    quarantined = (
        sorted(n for n in os.listdir(ckdir) if ".corrupt" in n)
        if os.path.isdir(ckdir)
        else []
    )
    job = job_ref.get("job")
    res = {
        "scenario": name,
        "workdir": workdir,
        "trace_dir": trace_dir,
        "timed_out": timed_out,
        "rc": result.get("rc"),
        "exc": result.get("exc"),
        "records": records,
        "quarantined": quarantined,
        "diagnostics": diagnostics,
        # Driver-side evidence: per-host health strikes and consumed
        # guard divergence reports (the silent scenario asserts both).
        "host_health": (
            job.driver.host_manager.host_health() if job is not None else {}
        ),
        "guard_reports": (
            {h: strikes for h, (_, strikes) in job._guard_reports.items()}
            if job is not None
            else {}
        ),
        # Control-plane evidence: how many times the KV listener was
        # chaos-restarted (kv_server_crash) — zero means the fault
        # never landed and the scenario proved nothing.
        "kv_restarts": job.server.restarts if job is not None else 0,
        # Goodput evidence: the driver ledger's wall-clock attribution
        # (crash/hang must book their outage as rescale_downtime).
        "goodput": (
            job._goodput.snapshot()
            if job is not None and job._goodput is not None
            else None
        ),
    }
    _goodput._reset_for_tests()
    if name in ("quant", "silent"):
        # The invariant is relative, not analytic: run the same worker
        # fault-free and demand bit-identical final params.
        res["baseline"] = run_scenario(
            f"{name}_baseline", steps=steps, timeout=timeout, seed=seed
        )
    return res


def run_driver_crash_scenario(steps: int = DEFAULT_STEPS,
                              workdir: Optional[str] = None,
                              timeout: float = 180.0, seed: int = 0) -> dict:
    """Driver death + crash-adoption, end to end, with history to lose:

    phase 0 — a worker hard-crashes at commit 2, is blacklisted (strike
    recorded, cooldown 1 s) and respawned on probation into round 2;
    phase 1 — the ``driver.crash`` chaos site kills the driver in round
    2 (cleanup suppressed: the KV dies with it, the workers are
    orphaned mid-run and block only on KV availability);
    phase 2 — a fresh driver with ``adopt=True`` replays the journal:
    same secret, same port, same round, same blacklist ledger —
    re-attaches the live workers by journaled pid and shepherds the job
    to completion WITHOUT restarting anything healthy.

    Invariants checked by :func:`check_invariants`: rc=0, exact step
    count and bit-identical analytic finals, the survivor never
    restarted from disk, the victim's blacklist strike survived the
    adoption, and at least one worker really was adopted (not
    respawned).
    """
    from unittest import mock

    from horovod_tpu import chaos as _chaos
    from horovod_tpu.runner import elastic_driver as ed

    # The crash is anchored to round 2 (the probation-respawn round,
    # ~2 s in); the survivor must still be mid-run THEN and through the
    # adoption — floor the step count so pacing × steps outlasts the
    # outage with margin (the result carries the effective count for
    # check_invariants).
    steps = max(steps, 8)
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_driver_crash_")
    journal_dir = os.path.join(workdir, "journal")
    with open(os.path.join(workdir, "hosts.txt"), "w") as f:
        f.write("localhost:1\n127.0.0.1:1\n")
    disco = os.path.join(workdir, "discover.sh")
    with open(disco, "w") as f:
        f.write(f"#!/bin/sh\ncat {workdir}/hosts.txt\n")
    os.chmod(disco, os.stat(disco).st_mode | stat.S_IEXEC)
    worker_py = os.path.join(workdir, "worker.py")
    with open(worker_py, "w") as f:
        f.write(WORKER)

    driver_env = {
        "HVDTPU_BLACKLIST_COOLDOWN": "1.0",
        "HVT_DATA_TIMEOUT_SECS": "10",
    }
    env = {
        "HVDTPU_TEST_WORKDIR": workdir,
        "HVDTPU_TEST_SOAK_STEPS": str(steps),
        "HVDTPU_ELASTIC_POLL_SECS": "0.1",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
        "JAX_PLATFORMS": "cpu",
        # Commits are paced so neither the blacklist/probation window
        # nor the driver outage can be outrun by the workers finishing.
        # Rule ORDER matters: site matching is first-match-wins, so the
        # narrowly-conditioned crash must precede the every-commit slow.
        "HVDTPU_CHAOS": (
            "worker.step:crash@step=2;host=127.0.0.1;spawn=0,"
            "worker.step:slow=0.3"
        ),
        "HVDTPU_CHAOS_SEED": str(seed),
    }
    env.update(driver_env)
    _arm_trace(workdir, env)

    # Armed across BOTH driver incarnations: the dying driver journals
    # its ledger inside `_driver_state()`, the adopter restores it and
    # books the takeover gap as `adoption_gap` — check_invariants
    # demands that gap is really on the adopted ledger.
    from horovod_tpu.obs import goodput as _goodput

    _goodput._reset_for_tests()
    _goodput.enable()

    result: dict = {}
    job_ref: dict = {}
    deadline = time.time() + timeout

    def _run(adopt: bool, key: str):
        try:
            with mock.patch.dict(os.environ, driver_env), mock.patch.object(
                ed, "DISCOVER_HOSTS_FREQUENCY_SECS", 0.1
            ):
                result[key] = ed.run_elastic(
                    [sys.executable, worker_py],
                    discovery_script=disco,
                    min_np=1,
                    reset_limit=10,
                    extra_env=env,
                    verbose=True,
                    output_dir=os.path.join(workdir, "logs"),
                    drain_timeout=30.0,
                    job_ref=job_ref,
                    journal_dir=journal_dir,
                    adopt=adopt,
                )
        except BaseException as exc:
            result[f"{key}_exc"] = repr(exc)

    # Phase 0/1: original driver, armed to die in round 2 (the round
    # that respawns the struck worker, so the blacklist ledger holds
    # real history when the crash lands).
    _chaos.plan("driver.crash:crash@step=2;n=1", seed=seed)
    t1 = threading.Thread(target=_run, args=(False, "rc1"), daemon=True)
    t1.start()
    t1.join(timeout=max(5.0, deadline - time.time()))
    _chaos.clear()
    phase1_timed_out = t1.is_alive()
    if phase1_timed_out:
        _teardown_job(job_ref.get("job"))
        t1.join(timeout=10.0)

    # Phase 2: respawned driver adopts the journaled state and the
    # orphaned (still-running) workers.
    adopted_hosts: List[str] = []
    timed_out = phase1_timed_out
    if not phase1_timed_out:
        job_ref.clear()
        t2 = threading.Thread(target=_run, args=(True, "rc"), daemon=True)
        t2.start()
        t2.join(timeout=max(5.0, deadline - time.time()))
        timed_out = t2.is_alive()
        if timed_out:
            _teardown_job(job_ref.get("job"))
            t2.join(timeout=10.0)
        job2 = job_ref.get("job")
        if job2 is not None:
            adopted_hosts = list(job2.adopted_hosts)
    else:
        job2 = None

    diagnostics = None
    if timed_out:
        diagnostics = _timeout_diagnostics(workdir, job_ref.get("job"))
        _attach_flight_recorder(diagnostics, workdir)
        print(
            "chaos_soak: driver_crash scenario blew its deadline; "
            f"diagnostics:\n{json.dumps(diagnostics, indent=1)}",
            file=sys.stderr, flush=True,
        )
    _disarm_trace()

    records: List[dict] = []
    progress = os.path.join(workdir, "progress.jsonl")
    if os.path.exists(progress):
        with open(progress) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    pass
    res = {
        "scenario": "driver_crash",
        "steps": steps,
        "workdir": workdir,
        "timed_out": timed_out,
        "rc": result.get("rc"),
        "exc": result.get("rc_exc"),
        "crash_exc": result.get("rc1_exc"),  # must name DriverCrashed
        "records": records,
        "quarantined": [],
        "diagnostics": diagnostics,
        "adopted_hosts": adopted_hosts,
        "adopted_epoch": (
            job2._epoch_gen if job2 is not None else None
        ),
        "host_health": (
            job2.driver.host_manager.host_health()
            if job2 is not None else {}
        ),
        "guard_reports": {},
        "kv_restarts": 0,
        # The ADOPTER's ledger: carries the dead driver's journaled
        # totals plus the takeover gap booked as adoption_gap.
        "goodput": (
            job2._goodput.snapshot()
            if job2 is not None and job2._goodput is not None
            else None
        ),
    }
    _goodput._reset_for_tests()
    return res


# Autotune worker (the `autotune` scenario): joins the elastic world
# like a training worker and drives the worker half of the closed-loop
# autotuner against the REAL journaled KV plane — but scores each trial
# with a DETERMINISTIC analytic duration (a smooth bowl over the
# normalized knob vector) instead of wall time, so a fault-free run and
# a crash-interrupted run must converge to the IDENTICAL final knob
# vector iff the search resumes from journaled history (proposals are a
# pure function of seed + history). Retrace-knob switches arrive as
# ordinary round republishes (HostsUpdatedInterrupt at commit), so the
# scenario also exercises the rescale-path leg of the rollout protocol.
WORKER_AUTOTUNE = '''
import json, os, sys, time

import horovod_tpu.native as native
from horovod_tpu import elastic
from horovod_tpu import tune
from horovod_tpu.elastic import worker as _ew

workdir = os.environ["HVDTPU_TEST_WORKDIR"]
host_id = os.environ["HVDTPU_HOST_ID"]


def log(rec):
    with open(os.path.join(workdir, "progress.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\\n")


native.init()
registry = tune.training_space()  # same env-derived space as the driver
client = tune.AutotuneClient(
    registry,
    _ew.tune_config_source(),
    scorer=tune.WindowScorer(),  # window/warmup from the env knobs
)

import numpy as _np
from horovod_tpu.analysis import certify as _cert
from horovod_tpu.ops.fusion import bucket_byte_layout as _layout

_CERT_PARAMS = {"w": _np.zeros((256, 64), _np.float32),
                "b": _np.zeros((64,), _np.float32)}
_n_retraces = 0


def retrace_cert():
    # The retrace-sensitive cert surface without a traced model: the
    # wire layout the rebuilt step would derive from the env the
    # lockstep switch just wrote (bucket_byte_layout reads the fusion
    # threshold from the env). Ranks that applied the same switch must
    # publish the same digest.
    wire = [[str(d), int(n)] for d, n in _layout(_CERT_PARAMS)]
    return _cert.ScheduleCert(
        digest=_cert._digest([], native.size(), wire),
        n_collectives=0, entries=(), world=native.size(),
        wire=tuple(tuple(w) for w in wire))


def fake_ms(vector):
    # Deterministic bowl with an interior optimum: identical on every
    # rank and every run, so trial history is bit-reproducible.
    u = registry.to_unit(vector)
    return 100.0 + 50.0 * sum((ui - 0.35) ** 2 for ui in u)


state = elastic.ObjectState(step=0)


@elastic.run
def train(st):
    while not client.done:
        act = client.step_start()
        if act is not None:
            log({"host": host_id, "rank": native.rank(),
                 "trial": client.applied_trial, "at_step": client.step,
                 "vector": client.applied, "retrace": bool(act.retrace)})
            if act.retrace:
                # The real preflight protocol over the real KV: publish
                # the rebuilt cert under a retraceN tag and verify the
                # peers match (warn mode + short timeout keep the soak
                # bounded; the checker asserts digest equality below).
                global _n_retraces
                _n_retraces += 1
                cert = retrace_cert()
                chan = _ew.cert_channel()
                rep = None
                if chan is not None:
                    rep = chan.preflight(
                        cert, tag="retrace%d" % _n_retraces,
                        mode="warn", timeout=5.0)
                log({"host": host_id, "rank": native.rank(),
                     "retrace_n": _n_retraces,
                     "retrace_cert": cert.digest,
                     "cert_ok": None if rep is None else rep["ok"]})
        time.sleep(0.02)
        vec = client.applied or registry.canonical(
            registry.default_vector()
        )
        client.step_end(fake_ms(vec) / 1e3)
        st.step += 1
        st.commit()
    return st.step


train(state)
log({"host": host_id, "rank": native.rank(),
     "autotune_final": client.applied, "final_trial": client.applied_trial,
     "steps_run": client.step})
native.shutdown()
'''


# Small, fast search: both phases of the scenario (and the baseline)
# must share these so the trial histories are comparable.
AUTOTUNE_SOAK_ENV = {
    "HVDTPU_AUTOTUNE": "1",
    "HVDTPU_AUTOTUNE_WINDOW_STEPS": "2",
    "HVDTPU_AUTOTUNE_WARMUP_STEPS": "1",
    "HVDTPU_AUTOTUNE_MAX_TRIALS": "5",
    "HVDTPU_AUTOTUNE_PATIENCE": "3",
    "HVDTPU_AUTOTUNE_SEED": "20240731",
    # The full knob catalog — the scenario deliberately exercises the
    # categorical layout arm and the retrace-knob round-republish leg
    # (the default selection would tune the fusion threshold only).
    "HVDTPU_AUTOTUNE_KNOBS": (
        "FUSION_THRESHOLD,OVERLAP_STAGGER,PREFETCH_DEPTH,"
        "COLLECTIVE_LAYOUT"
    ),
}


def run_autotune_scenario(workdir: Optional[str] = None,
                          timeout: float = 240.0, seed: int = 0,
                          crash: bool = True) -> dict:
    """Closed-loop autotune under driver crash-adoption:

    phase 0 — a 2-host elastic job tunes over the journaled KV plane
    (driver-side GP-EI coordinator, worker-side lockstep clients with
    deterministic analytic scores);
    phase 1 — ``driver.crash`` kills the driver at round 2 (rounds
    advance with every retrace-knob switch, so round 2 is mid-search);
    phase 2 — a fresh ``--adopt`` driver replays the journal, restores
    the search FROM THE JOURNALED TRIAL HISTORY, and shepherds the
    search to convergence.

    ``crash=False`` runs the fault-free twin. Invariants
    (:func:`check_autotune_invariants`): both runs rc=0, the crash
    really fired, the adopter held non-empty trial history at adoption
    (resumed, not re-learned), and the final knob vector is IDENTICAL
    to the fault-free run's.
    """
    from unittest import mock

    from horovod_tpu import chaos as _chaos
    from horovod_tpu.runner import elastic_driver as ed

    workdir = workdir or tempfile.mkdtemp(prefix="chaos_autotune_")
    os.makedirs(workdir, exist_ok=True)  # the baseline twin nests one
    journal_dir = os.path.join(workdir, "journal")
    with open(os.path.join(workdir, "hosts.txt"), "w") as f:
        f.write("localhost:1\n127.0.0.1:1\n")
    disco = os.path.join(workdir, "discover.sh")
    with open(disco, "w") as f:
        f.write(f"#!/bin/sh\ncat {workdir}/hosts.txt\n")
    os.chmod(disco, os.stat(disco).st_mode | stat.S_IEXEC)
    worker_py = os.path.join(workdir, "worker.py")
    with open(worker_py, "w") as f:
        f.write(WORKER_AUTOTUNE)

    driver_env = dict(AUTOTUNE_SOAK_ENV)
    env = {
        "HVDTPU_TEST_WORKDIR": workdir,
        "HVDTPU_ELASTIC_POLL_SECS": "0.1",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
        "JAX_PLATFORMS": "cpu",
    }
    env.update(AUTOTUNE_SOAK_ENV)
    _arm_trace(workdir, env)

    result: dict = {}
    job_ref: dict = {}
    deadline = time.time() + timeout

    def _run(adopt: bool, key: str):
        try:
            with mock.patch.dict(os.environ, driver_env), mock.patch.object(
                ed, "DISCOVER_HOSTS_FREQUENCY_SECS", 0.1
            ):
                result[key] = ed.run_elastic(
                    [sys.executable, worker_py],
                    discovery_script=disco,
                    min_np=1,
                    reset_limit=10,
                    extra_env=env,
                    verbose=True,
                    output_dir=os.path.join(workdir, "logs"),
                    drain_timeout=30.0,
                    job_ref=job_ref,
                    journal_dir=journal_dir,
                    adopt=adopt,
                )
        except BaseException as exc:
            result[f"{key}_exc"] = repr(exc)

    adopted_history_len = None
    timed_out = False
    if crash:
        # Phase 0/1: the original driver, armed to die mid-search
        # (round 2 = a couple of retrace switches in).
        _chaos.plan("driver.crash:crash@step=2;n=1", seed=seed)
        t1 = threading.Thread(target=_run, args=(False, "rc1"), daemon=True)
        t1.start()
        t1.join(timeout=max(5.0, deadline - time.time()))
        _chaos.clear()
        timed_out = t1.is_alive()
        if timed_out:
            _teardown_job(job_ref.get("job"))
            t1.join(timeout=10.0)
        job2 = None
        if not timed_out:
            job_ref.clear()
            t2 = threading.Thread(target=_run, args=(True, "rc"), daemon=True)
            t2.start()
            t2.join(timeout=max(5.0, deadline - time.time()))
            timed_out = t2.is_alive()
            if timed_out:
                _teardown_job(job_ref.get("job"))
                t2.join(timeout=10.0)
            job2 = job_ref.get("job")
            if job2 is not None and job2._adopted_state:
                at = job2._adopted_state.get("autotune") or {}
                adopted_history_len = len(
                    (at.get("search") or {}).get("ys", [])
                )
    else:
        t1 = threading.Thread(target=_run, args=(False, "rc"), daemon=True)
        t1.start()
        t1.join(timeout=max(5.0, deadline - time.time()))
        timed_out = t1.is_alive()
        if timed_out:
            _teardown_job(job_ref.get("job"))
            t1.join(timeout=10.0)
        job2 = job_ref.get("job")

    diagnostics = None
    if timed_out:
        diagnostics = _timeout_diagnostics(workdir, job_ref.get("job"))
        _attach_flight_recorder(diagnostics, workdir)
        print(
            "chaos_soak: autotune scenario blew its deadline; "
            f"diagnostics:\n{json.dumps(diagnostics, indent=1)}",
            file=sys.stderr, flush=True,
        )
    _disarm_trace()

    records: List[dict] = []
    progress = os.path.join(workdir, "progress.jsonl")
    if os.path.exists(progress):
        with open(progress) as f:
            for line in f:
                try:
                    records.append(json.loads(line))
                except ValueError:
                    pass
    tuner = getattr(job2, "_tuner", None) if job2 is not None else None
    res = {
        "scenario": "autotune",
        "workdir": workdir,
        "timed_out": timed_out,
        "rc": result.get("rc"),
        "exc": result.get("rc_exc"),
        "crash_exc": result.get("rc1_exc"),  # must name DriverCrashed
        "records": records,
        "quarantined": [],
        "diagnostics": diagnostics,
        "adopted_history_len": adopted_history_len,
        "final_trials": (
            tuner.search.n_trials if tuner is not None else None
        ),
        "final_vector": (
            tuner.search.best_vector() if tuner is not None
            and tuner.search.n_trials else None
        ),
        "kv_restarts": 0,
        "host_health": (
            job2.driver.host_manager.host_health()
            if job2 is not None else {}
        ),
        "guard_reports": {},
    }
    if crash:
        # The fault-free twin the final config must match bit-for-bit.
        res["baseline"] = run_autotune_scenario(
            workdir=os.path.join(workdir, "baseline"),
            timeout=max(30.0, deadline - time.time() + timeout / 2),
            seed=seed, crash=False,
        )
    return res


def check_autotune_invariants(res: dict) -> List[str]:
    """Violated invariants for the autotune scenario ([] = survived)."""
    problems: List[str] = []
    if res["timed_out"]:
        return ["autotune: job did not finish in time"]
    if res.get("exc"):
        return [f"autotune: driver raised {res['exc']}"]
    if res["rc"] != 0:
        problems.append(f"autotune: job rc={res['rc']}, wanted 0")
    finals = [r for r in res["records"] if "autotune_final" in r]
    if not finals:
        problems.append("autotune: no worker reported a final vector")
        return problems
    vectors = {json.dumps(r["autotune_final"], sort_keys=True)
               for r in finals}
    if len(vectors) != 1:
        problems.append(
            f"autotune: ranks disagree on the final vector: {vectors}"
        )
    base = res.get("baseline")
    if base is not None:
        # The headline invariant: a crash mid-search converges to the
        # SAME config the fault-free run found — resumed from journaled
        # history, never re-learned.
        if not res.get("crash_exc") or "DriverCrashed" not in res["crash_exc"]:
            problems.append(
                "autotune: the driver never crashed "
                f"(phase-1 outcome: {res.get('crash_exc')!r})"
            )
        if not res.get("adopted_history_len"):
            problems.append(
                "autotune: adopter held no journaled trial history — the "
                "search restarted instead of resuming"
            )
        problems.extend(check_autotune_invariants(base))
        base_finals = [
            r for r in base.get("records", []) if "autotune_final" in r
        ]
        if base_finals and finals:
            want = json.dumps(
                base_finals[-1]["autotune_final"], sort_keys=True
            )
            got = json.dumps(finals[-1]["autotune_final"], sort_keys=True)
            if want != got:
                problems.append(
                    "autotune: post-crash final vector diverges from the "
                    f"fault-free run ({got} vs {want}) — the resumed "
                    "search did not replay the journaled history"
                )
        if (base.get("final_trials") is not None
                and res.get("final_trials") is not None
                and base["final_trials"] != res["final_trials"]):
            problems.append(
                f"autotune: trial count {res['final_trials']} != "
                f"fault-free {base['final_trials']}"
            )
    # No rank ever ran a mixed vector: every switch record for a trial
    # names the same step boundary and vector on every rank.
    by_trial: Dict[int, set] = {}
    for r in res["records"]:
        if "trial" in r and "at_step" in r:
            by_trial.setdefault(r["trial"], set()).add(
                (r["at_step"], json.dumps(r["vector"], sort_keys=True))
            )
    for trial, switches in sorted(by_trial.items()):
        if len(switches) != 1:
            problems.append(
                f"autotune: trial {trial} switched unevenly across "
                f"ranks: {sorted(switches)}"
            )
    # Every lockstep retrace rebuilt the SAME program: per retrace
    # round, all ranks published identical schedule-cert digests
    # through the KV preflight (a divergent digest here is the mixed-
    # build pod hang the certify plane exists to catch).
    by_retrace: Dict[int, set] = {}
    for r in res["records"]:
        if "retrace_cert" in r:
            by_retrace.setdefault(r["retrace_n"], set()).add(
                r["retrace_cert"]
            )
    for n, digests in sorted(by_retrace.items()):
        if len(digests) != 1:
            problems.append(
                f"autotune: retrace {n} published divergent certs "
                f"across ranks: {sorted(digests)}"
            )
    return problems


def _arm_trace(workdir: str, env: dict) -> str:
    """Arm the tracing plane for a scenario: subprocess workers via the
    env block, the in-process driver programmatically (same recorder,
    ``driver`` stem). Every soak run ships flight-recorder evidence —
    the ring is bounded, so this costs a few MB per scenario at most."""
    from horovod_tpu.obs import trace as _trace

    trace_dir = os.path.join(workdir, "trace")
    env["HVDTPU_TRACE"] = "1"
    env["HVDTPU_TRACE_DIR"] = trace_dir
    _trace.enable(directory=trace_dir)
    return trace_dir


def _disarm_trace() -> None:
    """Scenario over: dump whatever the in-process side recorded, then
    disarm AND clear the ring — the next scenario's dumps must not
    carry this one's wall-clock-stamped history as fake evidence."""
    from horovod_tpu.obs import trace as _trace

    _trace.flight_dump("scenario_end")
    _trace.disable()
    _trace.set_role(None)
    _trace.recorder().clear()


def _attach_flight_recorder(diag, workdir: str):
    """Merge the per-process flight-recorder dumps the teardown just
    produced (workers dump on the kill SIGTERM; a chaos ``hang``/
    ``crash`` victim dumped at injection time) into one clock-aligned
    timeline and attach it to the deadline diagnostics. Returns the
    diagnostics dict for chaining."""
    import tools.hvdtpu_trace as ht

    from horovod_tpu.obs import trace as _trace

    diag = diag if diag is not None else {}
    _trace.flight_dump("deadline")
    trace_dir = os.path.join(workdir, "trace")
    out = os.path.join(trace_dir, "merged.json")
    try:
        merged = ht.merge_dir(trace_dir, out=out)
    except Exception as e:  # noqa: BLE001 - diagnostics only
        diag["flight_recorder"] = {"error": repr(e)}
        return diag
    if merged is None:
        diag["flight_recorder"] = {"error": "no flight-recorder dumps"}
        return diag
    diag["flight_recorder"] = {
        "merged": out,
        "files": [os.path.basename(p) for p in ht.discover(trace_dir)],
        "events": len(merged["traceEvents"]),
        "clock_offsets_us": merged["metadata"].get("clock_offsets_us"),
    }
    return diag


def _timeout_diagnostics(workdir: str, job=None, tail_bytes: int = 4000):
    """Evidence bundle for a scenario that blew its deadline: the tail
    of every worker/driver log plus the KV plane's last round state
    (round pointer, per-host assignments, heartbeat tokens, guard
    reports) — enough to see WHERE the job wedged without re-running."""
    diag: dict = {"log_tail": {}, "kv": {}}
    paths = [os.path.join(workdir, "progress.jsonl")]
    logs_dir = os.path.join(workdir, "logs")
    for dirpath, _, names in os.walk(logs_dir):
        paths.extend(os.path.join(dirpath, n) for n in names)
    for p in paths:
        try:
            with open(p, "rb") as f:
                f.seek(max(0, os.path.getsize(p) - tail_bytes))
                diag["log_tail"][os.path.relpath(p, workdir)] = (
                    f.read().decode("utf-8", "replace")
                )
        except OSError:
            continue
    if job is not None:
        def scope(name):
            try:
                return {
                    k: v.decode("utf-8", "replace")
                    for k, v in job.server.scope_items(name).items()
                }
            except Exception as e:  # noqa: BLE001 - diagnostics only
                return {"error": repr(e)}

        diag["kv"]["elastic"] = scope("elastic")
        rnd = diag["kv"]["elastic"].get("round")
        if rnd is not None:
            diag["kv"][f"round_{rnd}"] = scope(f"round_{rnd}")
        diag["kv"]["heartbeat"] = scope("heartbeat")
        diag["kv"]["guard"] = scope("guard")
    return diag


def _teardown_job(job) -> None:
    """Best-effort demolition of a wedged ElasticJob from outside its
    run loop (the loop's own finally does the same; this unsticks it)."""
    if job is None:
        return
    for fn in (
        job._terminate_all,
        job.driver.stop,
        job.server.stop,
    ):
        try:
            fn()
        except Exception:  # noqa: BLE001 - already past the deadline
            pass


def check_invariants(res: dict, steps: int = DEFAULT_STEPS) -> List[str]:
    """Violated invariants for one scenario result ([] = survived)."""
    name = res["scenario"]
    # A scenario may floor the step count for pacing reasons; its
    # result carries the effective target it actually ran with.
    steps = res.get("steps", steps)
    if name.startswith("serve"):
        return check_serve_invariants(res)
    if name.startswith("decode"):
        return check_decode_invariants(res)
    if name.startswith("stream"):
        return check_stream_invariants(res)
    if name == "autotune":
        return check_autotune_invariants(res)
    problems: List[str] = []
    if res["timed_out"]:
        return [f"{name}: job did not finish in time"]
    if res.get("exc"):
        return [f"{name}: driver raised {res['exc']}"]
    if res["rc"] != 0:
        problems.append(f"{name}: job rc={res['rc']}, wanted 0")
    finals = [r for r in res["records"] if "final_step" in r]
    if not finals:
        problems.append(f"{name}: no worker reported a final step")
        return problems
    # Step-count invariant: every finishing rank reached exactly the
    # target step — nothing lost to the fault, nothing double-run.
    for r in finals:
        if r["final_step"] != steps:
            problems.append(
                f"{name}: {r['host']} finished at step {r['final_step']}, "
                f"wanted {steps}"
            )
    # Restored-state invariant: final params match the analytic fault-
    # free value exactly (the update is a pure function of the step).
    # The quant/silent scenarios' update is a real jax step, so their
    # invariant is relative (vs the fault-free baseline run) not
    # analytic.
    if not name.startswith(("quant", "silent")):
        want = -LEARNING_RATE * GRAD * steps
        for r in finals:
            for x in r["final_w"]:
                if abs(x - want) > 1e-9:
                    problems.append(
                        f"{name}: {r['host']} final_w={r['final_w']}, "
                        f"wanted all {want}"
                    )
                    break
    # Scenario-specific evidence the intended recovery path ran.
    if name == "ckpt":
        if not res["quarantined"]:
            problems.append(
                "ckpt: no quarantined .corrupt checkpoint directory"
            )
        if not any("resumed_at" in r for r in res["records"]):
            problems.append("ckpt: restarted worker never resumed from disk")
    if name in ("crash", "hang"):
        sizes = {r["size"] for r in res["records"] if "size" in r}
        if sizes != {1, 2}:
            problems.append(
                f"{name}: expected the world to shrink 2→1, saw sizes {sizes}"
            )
        # Attribution invariant: the fault's lost wall-clock landed in
        # the right ledger category. A rescale (blacklist + republish
        # after the crash/lease-expiry) must book rescale_downtime on
        # the driver ledger — the recovery succeeding is not enough,
        # the downtime must also be ACCOUNTED.
        gp = res.get("goodput")
        if not gp:
            problems.append(f"{name}: driver goodput ledger missing")
        elif gp["totals"].get("rescale_downtime", 0.0) <= 0.0:
            problems.append(
                f"{name}: no rescale_downtime on the driver ledger "
                f"(totals: { {k: round(v, 3) for k, v in gp['totals'].items() if v > 0} })"
            )
        survivor = [
            r for r in res["records"]
            if r.get("host") == "localhost" and "step" in r
        ]
        step_seq = [r["step"] for r in survivor]
        if step_seq != sorted(step_seq):
            problems.append(f"{name}: survivor's step sequence regressed")
    if name == "kv_outage":
        # Nobody may have restarted: both hosts log every step once.
        for host in ("localhost", "127.0.0.1"):
            seq = [
                r["step"] for r in res["records"]
                if r.get("host") == host and "step" in r
            ]
            if seq != list(range(1, steps + 1)):
                problems.append(
                    f"kv_outage: {host} step sequence {seq} shows a restart"
                )
    if name == "straggler":
        hosts_done = {r["host"] for r in finals}
        if hosts_done != {"localhost", "127.0.0.1"}:
            problems.append(
                f"straggler: only {hosts_done} finished — the slow rank "
                "was killed instead of waited for"
            )
    if name == "preempt":
        # The eviction resolved through the GRACE path: world shrank
        # 2→1, the victim took a manifest-verified priority checkpoint
        # and left WITHOUT finishing — and nobody was blacklisted.
        sizes = {r["size"] for r in res["records"] if "size" in r}
        if sizes != {1, 2}:
            problems.append(
                f"preempt: expected the world to shrink 2→1, saw {sizes}"
            )
        if {r["host"] for r in finals} != {"localhost"}:
            problems.append(
                "preempt: the evicted host finished instead of draining "
                f"({sorted(r['host'] for r in finals)})"
            )
        ckpts = [r for r in res["records"] if "preempt_ckpt" in r]
        if not any(r.get("host") == "127.0.0.1" for r in ckpts):
            problems.append(
                "preempt: the victim never took a priority checkpoint"
            )
        if res.get("host_health"):
            problems.append(
                "preempt: the drained host was blacklisted/penalized "
                f"({res['host_health']}) — eviction must not cost strikes"
            )
        pdir = os.path.join(res["workdir"], "preempt_ckpt")
        from horovod_tpu import checkpoint as _ckpt

        psteps = _ckpt.all_steps(pdir)
        if not psteps:
            problems.append("preempt: no priority checkpoint on disk")
        else:
            bad = _ckpt.verify_step_dir(
                os.path.join(pdir, f"step_{psteps[-1]}")
            )
            if bad:
                problems.append(
                    f"preempt: priority checkpoint fails integrity: {bad[:2]}"
                )
    if name == "kv_server_crash":
        # The KV listener really died (≥1 chaos restart), and nobody
        # even flinched: every host logs every step exactly once, no
        # worker restarted from disk, no host was blacklisted.
        if res.get("kv_restarts", 0) < 1:
            problems.append(
                "kv_server_crash: the KV server was never restarted — "
                "the fault did not land"
            )
        for host in ("localhost", "127.0.0.1"):
            seq = [
                r["step"] for r in res["records"]
                if r.get("host") == host and "step" in r
            ]
            if seq != list(range(1, steps + 1)):
                problems.append(
                    f"kv_server_crash: {host} step sequence {seq} shows "
                    "a restart during the KV outage"
                )
        if any("resumed_at" in r for r in res["records"]):
            problems.append(
                "kv_server_crash: a worker restarted from disk during "
                "the KV outage"
            )
        if res.get("host_health"):
            problems.append(
                "kv_server_crash: hosts were struck for a control-plane "
                f"fault: {res['host_health']}"
            )
    if name == "driver_crash":
        if not res.get("crash_exc") or "DriverCrashed" not in res["crash_exc"]:
            problems.append(
                "driver_crash: the driver never crashed "
                f"(phase-1 outcome: {res.get('crash_exc')!r})"
            )
        if not res.get("adopted_hosts"):
            problems.append(
                "driver_crash: the adopter re-attached no live workers — "
                "healthy workers were restarted instead"
            )
        if res.get("adopted_epoch") != 1:
            problems.append(
                f"driver_crash: adopted driver epoch "
                f"{res.get('adopted_epoch')}, wanted 1"
            )
        if res.get("host_health", {}).get("127.0.0.1", 0) < 1:
            problems.append(
                "driver_crash: the victim's blacklist strike did not "
                "survive the adoption"
            )
        resumed = {
            r["host"] for r in res["records"] if "resumed_at" in r
        }
        if "localhost" in resumed:
            problems.append(
                "driver_crash: the healthy survivor restarted from disk "
                "during the driver outage"
            )
        # Attribution invariant: the driver outage itself (dead
        # driver's last journal write → adopter takeover) is booked as
        # adoption_gap on the ADOPTED ledger, proving the ledger state
        # rode the journal across the crash.
        gp = res.get("goodput")
        if not gp:
            problems.append(
                "driver_crash: adopted driver goodput ledger missing"
            )
        elif gp["totals"].get("adoption_gap", 0.0) <= 0.0:
            problems.append(
                "driver_crash: no adoption_gap on the adopted ledger "
                f"(totals: { {k: round(v, 3) for k, v in gp['totals'].items() if v > 0} })"
            )
    if name == "quant":
        base = res.get("baseline") or {}
        base_finals = [
            r for r in base.get("records", []) if "final_step" in r
        ]
        if base.get("rc") != 0 or not base_finals:
            problems.append(
                f"quant: fault-free baseline run failed "
                f"(rc={base.get('rc')})"
            )
        else:
            # Bit-identical final params: the crashed run resumed from
            # the checkpointed TrainState (params + opt + EF residuals)
            # and replayed the identical remaining trajectory.
            if finals[-1]["final_w"] != base_finals[-1]["final_w"]:
                problems.append(
                    "quant: post-crash final params diverge from the "
                    f"fault-free baseline ({finals[-1]['final_w']} vs "
                    f"{base_finals[-1]['final_w']}) — EF/optimizer state "
                    "did not survive the restore"
                )
        resumes = [r for r in res["records"] if "resumed_at" in r]
        if not resumes:
            problems.append(
                "quant: worker never resumed from disk (crash did not "
                "fire or restore path was skipped)"
            )
        elif not any(
            r.get("resume_residual_norm", 0) > 0 for r in resumes
        ):
            problems.append(
                "quant: resumed EF residuals are all-zero — the residual "
                "state did not round-trip through the checkpoint"
            )
    if name == "silent":
        problems.extend(_check_silent_invariants(res, finals))
    return problems


def _check_silent_invariants(res: dict, finals: List[dict]) -> List[str]:
    """The fail-silent scenario's evidence: every fault fired, every
    fault was caught by the INTENDED defense, nothing corrupt survived."""
    problems: List[str] = []
    # Bit-identical finals vs the fault-free baseline on EVERY host: the
    # nan skip lost no step and the bitflip resync restored the victim
    # exactly (the whole point of "fail-silent defense").
    base = res.get("baseline") or {}
    base_finals = [r for r in base.get("records", []) if "final_step" in r]
    if base.get("rc") != 0 or not base_finals:
        problems.append(
            f"silent: fault-free baseline run failed (rc={base.get('rc')})"
        )
    else:
        want = base_finals[-1]["final_w"]
        for r in finals:
            if r["final_w"] != want:
                problems.append(
                    f"silent: {r['host']} final params diverge from the "
                    "fault-free baseline — a fault escaped the guard"
                )
    # The NaN storm really fired and was screened in-graph on every rank
    # (skipped_total > 0 everywhere; the step totals still match, so the
    # skip retried rather than dropped the step).
    if not finals or any(r.get("skipped_total", 0) < 1 for r in finals):
        problems.append(
            "silent: a rank never skipped — grad.nan did not fire or the "
            "guard let it through"
        )
    # The bitflip was audit-detected within one window, localized to the
    # victim by majority vote, and healed by resync.
    audits = [
        r["audit"] for r in res["records"]
        if r.get("audit", {}).get("diverged")
    ]
    if not audits:
        problems.append(
            "silent: no audit round ever saw the bitflip divergence"
        )
    else:
        a = audits[0]
        if a.get("minority_hosts") != [SILENT_VICTIM]:
            problems.append(
                f"silent: audit localized {a.get('minority_hosts')}, "
                f"wanted [{SILENT_VICTIM!r}]"
            )
        if a.get("healed") != "resync":
            problems.append(
                f"silent: divergence healed by {a.get('healed')!r}, "
                "wanted 'resync'"
            )
    # The driver's health scoring consumed the divergence report.
    if res.get("guard_reports", {}).get(SILENT_VICTIM, 0) < 1:
        problems.append(
            "silent: the driver never consumed a divergence report for "
            "the victim"
        )
    if res.get("host_health", {}).get(SILENT_VICTIM, 0) < 1:
        problems.append(
            "silent: the victim carries no health strike after diverging"
        )
    # Zero corrupted checkpoints committed: nothing was quarantined and
    # every step directory on disk still passes its CRC manifest.
    if res["quarantined"]:
        problems.append(
            f"silent: corrupted checkpoints reached disk: "
            f"{res['quarantined']}"
        )
    ckdir = os.path.join(res["workdir"], "ckpt")
    if os.path.isdir(ckdir):
        from horovod_tpu import checkpoint as _ckpt

        for step_n in _ckpt.all_steps(ckdir):
            bad = _ckpt.verify_step_dir(
                os.path.join(ckdir, f"step_{step_n}")
            )
            if bad:
                problems.append(
                    f"silent: committed checkpoint step {step_n} fails "
                    f"integrity: {bad[:2]}"
                )
    else:
        problems.append("silent: no checkpoints were ever committed")
    return problems


def run_all(names: Optional[List[str]] = None, steps: int = DEFAULT_STEPS,
            seed: int = 0) -> dict:
    """Run the requested scenarios (default: all five); returns a
    report with per-scenario results and violated invariants."""
    names = names or SCENARIO_NAMES
    report = {"tool": "chaos_soak", "steps": steps, "seed": seed,
              "scenarios": {}, "ok": True}
    for name in names:
        res = run_scenario(name, steps=steps, seed=seed)
        problems = check_invariants(res, steps=steps)
        report["scenarios"][name] = {
            "ok": not problems,
            "rc": res["rc"],
            "problems": problems,
            "workdir": res["workdir"],
            "quarantined": res["quarantined"],
        }
        if problems:
            report["ok"] = False
    return report


def main() -> int:
    ap = argparse.ArgumentParser(prog="chaos_soak")
    ap.add_argument(
        "--scenario", default="all",
        help=f"one of: all, baseline, {', '.join(SCENARIO_NAMES)}",
    )
    ap.add_argument("--steps", type=int, default=DEFAULT_STEPS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args()
    names = (
        SCENARIO_NAMES if args.scenario == "all" else [args.scenario]
    )
    # The soak is a CPU program end to end: its workers are pinned to the
    # CPU platform, and the in-process scenarios (serve, decode, stream)
    # use JAX in THIS process — which must not attach a chip and then
    # hold it while it spawns workers.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    report = run_all(names, steps=args.steps, seed=args.seed)
    if args.json:
        print(json.dumps(report))
    else:
        for name, res in report["scenarios"].items():
            status = "OK" if res["ok"] else "FAIL"
            print(f"{name}: {status} (rc={res['rc']})")
            for p in res["problems"]:
                print(f"  {p}")
        print("chaos_soak:", "survived" if report["ok"] else "FAILED")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
