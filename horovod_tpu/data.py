"""Per-rank data sharding with mid-epoch elastic resume (JAX path).

The reference solves this per framework — ``torch.ElasticSampler``
(``horovod/torch/elastic/sampler.py:24``: shard by rank, track processed
indices, re-shard over the new world after a resize) and Spark's
Petastorm shards.  This is the framework-neutral equivalent for the JAX
training path: deterministic per-epoch shuffles, world-size sharding with
cycling padding, processed-index tracking for state-preserving restarts,
and a ``state_dict`` that plugs into :mod:`horovod_tpu.elastic` state and
:mod:`horovod_tpu.checkpoint`. :func:`prefetch_to_device` adds the input
leg of the overlap pipeline: double-buffered host→device staging so the
H2D copy of the next batch runs under the current step's compute.
"""

from __future__ import annotations

import collections
import math
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from .context import rank as _ctx_rank, size as _ctx_size
from .exceptions import NotInitializedError
from .obs import registry as _obs
from .utils import env as _env


def _world() -> tuple:
    try:
        return _ctx_rank(), _ctx_size()
    except NotInitializedError:
        # No world yet (unit tests, single-process scripts): shard as a
        # world of one. Any other context failure propagates — silently
        # degrading to world-of-1 would duplicate training data.
        return 0, 1


class ShardedIndexSampler:
    """Rank-sharded index stream with mid-epoch resume.

    Semantics mirror ``ElasticSampler``: each epoch is a seeded
    permutation; already-processed indices are excluded on ``reset()``
    (after an elastic restart or checkpoint restore); the remaining
    indices are padded by cycling so every rank yields the same count.
    """

    def __init__(self, num_items: int, *, shuffle: bool = True,
                 seed: int = 0, rank: Optional[int] = None,
                 world_size: Optional[int] = None):
        self.num_items = num_items
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = 0
        self.processed: set = set()
        self._rank_override = rank
        self._world_override = world_size
        self.reset()

    # -- world/epoch management -------------------------------------
    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        self.processed = set()
        self.reset()

    def record(self, indices: Sequence[int]) -> None:
        self.processed.update(int(i) for i in indices)

    def reset(self) -> None:
        rank, world = _world()
        self.rank = self._rank_override if self._rank_override is not None else rank
        self.world_size = (
            self._world_override
            if self._world_override is not None
            else world
        )
        order = np.arange(self.num_items)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            order = rng.permutation(order)
        if self.processed:
            done = np.fromiter(self.processed, np.int64, len(self.processed))
            remaining = order[~np.isin(order, done)].tolist()
        else:
            remaining = order.tolist()
        self.num_samples = math.ceil(len(remaining) / self.world_size)
        total = self.num_samples * self.world_size
        if remaining:
            pad = total - len(remaining)
            reps = -(-pad // len(remaining)) if pad > 0 else 0
            remaining = remaining + (remaining * reps)[:pad]
        self._indices = remaining

    # -- iteration ---------------------------------------------------
    def __iter__(self) -> Iterator[int]:
        return iter(self._indices[self.rank :: self.world_size])

    def __len__(self) -> int:
        return self.num_samples

    # -- persistence -------------------------------------------------
    def state_dict(self) -> Dict:
        return {
            "epoch": self.epoch,
            "processed": sorted(self.processed),
            "seed": self.seed,
        }

    def load_state_dict(self, state: Dict) -> None:
        self.epoch = int(state["epoch"])
        self.seed = int(state.get("seed", self.seed))
        self.processed = set(state["processed"])
        self.reset()


class ShardedBatches:
    """Batched numpy iterator over a :class:`ShardedIndexSampler`.

    Yields ``(batch_arrays..., indices)`` so callers can ``record()``
    what they consumed before committing elastic state.

    **Pad vs drop at the epoch boundary.** Two distinct tail effects
    compose here, and both must resolve to the *same* batch count on
    every rank or a rank finishes its epoch early and the next collective
    deadlocks — invisibly so when a prefetch wrapper
    (:func:`prefetch_to_device`) is pulling ``depth`` batches ahead of
    the training loop:

    1. ``num_items % world != 0`` — the sampler PADS by cycling, so every
       rank's index stream has the same length (never dropped; a few
       samples are seen twice per epoch).
    2. ``len(sampler) % batch_size != 0`` — the ragged final batch. With
       ``drop_remainder=True`` (default; static shapes for XLA) it is
       DROPPED — identically on every rank, because of (1) — and its
       *real* indices are intentionally NOT recorded, so a mid-epoch
       restore re-serves them instead of losing them. With
       ``drop_remainder=False`` the final batch is padded by cycling
       this rank's own index stream, keeping shapes static while every
       real sample is consumed every epoch (duplicates, like the
       sampler's, slightly overweight a few samples).
    """

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int,
                 sampler: Optional[ShardedIndexSampler] = None,
                 drop_remainder: bool = True, **kw):
        lengths = {len(a) for a in arrays}
        if len(lengths) != 1:
            raise ValueError(f"arrays disagree on length: {lengths}")
        self.arrays = list(arrays)
        self.batch_size = batch_size
        self.drop_remainder = drop_remainder
        # `is not None`, not truthiness: a sampler with an empty shard
        # (len 0, e.g. restored at epoch end) is falsy but must be kept.
        self.sampler = (
            sampler
            if sampler is not None
            else ShardedIndexSampler(lengths.pop(), **kw)
        )

    def __iter__(self):
        idx: List[int] = []
        # Pad source for the drop_remainder=False tail: the first
        # batch_size indices of this rank's stream are all the cycling
        # pad can ever read, so that is all that is kept (an epoch over
        # a huge shard must not accumulate every yielded index).
        seen: List[int] = []
        for i in self.sampler:
            idx.append(i)
            if not self.drop_remainder and len(seen) < self.batch_size:
                seen.append(i)
            if len(idx) == self.batch_size:
                sel = np.asarray(idx)
                yield tuple(a[sel] for a in self.arrays) + (sel,)
                idx = []
        if idx and not self.drop_remainder and seen:
            # Pad the ragged tail by cycling this rank's own stream (the
            # sampler's equal-length guarantee keeps the extra batch
            # count identical across ranks).
            k = 0
            while len(idx) < self.batch_size:
                idx.append(seen[k % len(seen)])
                k += 1
            sel = np.asarray(idx)
            yield tuple(a[sel] for a in self.arrays) + (sel,)

    def __len__(self) -> int:
        n, rem = divmod(len(self.sampler), self.batch_size)
        if rem and not self.drop_remainder:
            return n + 1
        return n


def prefetch_to_device(iterator, depth: Optional[int] = None, *,
                       sharding=None) -> Iterator:
    """Host→device input staging, ``depth`` batches ahead.

    Wrap a batch iterator (e.g. :class:`ShardedBatches`) so each element
    is staged onto device with ``jax.device_put`` up to ``depth`` items
    (default ``HVDTPU_PREFETCH_DEPTH``) before the training loop asks
    for it. There is no thread: the buffer is a deque refilled
    synchronously inside the consumer's ``next()``, so the source
    iterator's own work for batch ``n+depth`` runs on the loop's thread.
    What overlaps the device is the copy: ``device_put`` enqueues the
    transfer and returns, and a loop that dispatches steps
    asynchronously refills while earlier steps still execute. Ordering
    is preserved and the wrapper is exactly as long as its input
    (exhaustion passes through; no batch is dropped or duplicated).

    ``sharding`` (a ``jax.sharding.Sharding`` or device) is forwarded to
    ``device_put`` so batches can land pre-sharded over the world mesh.
    On CPU test platforms ``device_put`` is effectively synchronous —
    same semantics, no overlap.

    Spans (:func:`horovod_tpu.obs.trace.span`: profiler annotation
    always, ring event with ``HVDTPU_TRACE``): ``hvd.input.fill`` covers
    one refill (``stalled``: the buffer was empty, so the consumer waited
    for it; ``occupancy`` at entry; ``depth``) and, inside it,
    ``hvd.input.put`` each ``device_put`` alone, apart from the source's
    ``next()``. Neither is held open across a ``yield``. Always on:
    histogram ``input.put_ms`` and counter ``input.stalled`` (stalled
    refills; the first refill of a run is one). With the metrics plane
    on, gauges ``prefetch.depth`` / ``prefetch.occupancy`` (buffer fill
    seen at each yield) and counter ``prefetch.batches``.
    """
    if depth is None:
        depth = _env.prefetch_depth()
    if depth < 1:
        # Validated here, not in the generator: the error fires at wrap
        # time instead of at the first (possibly much later) next().
        raise ValueError(f"prefetch depth must be >= 1, got {depth}")

    import time as _time

    import jax  # deferred: the rest of this module is jax-free numpy

    from .obs import goodput as _goodput
    from .obs import trace as _trace

    def put(item):
        with _trace.span("hvd.input.put", "data"):
            t0 = _time.perf_counter()
            if sharding is not None:
                out = jax.device_put(item, sharding)
            else:
                out = jax.device_put(item)
            _obs.always().histogram("input.put_ms").observe(
                (_time.perf_counter() - t0) * 1e3
            )
        return out

    def gen():
        queue: collections.deque = collections.deque()
        it = iter(iterator)
        exhausted = False
        while True:
            if not exhausted:
                was_empty = not queue
                goodput_on = _goodput.enabled()
                if goodput_on:
                    w0, t0 = _time.time(), _time.perf_counter()
                filled = 0
                # The data-fetch + H2D-enqueue slice. An empty buffer at
                # entry means the consumer OUTRAN the prefetcher — this
                # span was a stall on the step's critical path, not
                # overlapped background work; ``stalled`` is how a
                # timeline tells the two apart.
                with _trace.span(
                    "hvd.input.fill", "data", stalled=was_empty,
                    occupancy=len(queue), depth=depth,
                ):
                    while len(queue) < depth:
                        try:
                            item = next(it)
                        except StopIteration:
                            exhausted = True
                            break
                        queue.append(put(item))
                        filled += 1
                if filled and was_empty:
                    _obs.always().counter("input.stalled").inc()
                    if goodput_on:
                        # This fill ran on the consumer's critical path:
                        # goodput-visible input stall.
                        _goodput.record_input_stall(
                            w0, _time.perf_counter() - t0
                        )
            if not queue:
                return
            # Enablement checked per yield (one cached boolean), matching
            # the step wrapper: obs.enable() mid-run starts producing
            # prefetch gauges on the next batch, not never.
            if _obs.enabled():
                reg = _obs.metrics()
                reg.gauge("prefetch.depth").set(depth)
                reg.gauge("prefetch.occupancy").set(len(queue))
                reg.counter("prefetch.batches").inc()
            yield queue.popleft()

    return gen()
