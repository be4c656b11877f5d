"""The one traffic generator: host batches from a traffic file's ``data``
parameters and ``--seed``. A traffic mix is data; this is the only code
that reads it.

``data`` parameters:

* ``next_token_shift``: 0, or 1 for a language-model batch that carries
  ``seq_len + 1`` tokens (inputs are ``[:, :-1]``, targets ``[:, 1:]``);
* ``lengths``: ``null`` (every sequence is ``seq_len`` long) or
  ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``:
  real lengths, clipped; the batch then carries ``attention_mask`` and the
  positions past a sequence's length hold ``pad_id``;
* ``labels``: ``null``, ``{"kind": "tokens"}`` (a target id for every
  position) or ``{"kind": "classes", "num_labels": n}`` (one per sequence).

Token ids are uniform over the vocabulary. Every array has the global batch
as its leading dimension, so one ``NamedSharding`` splits them all.
"""

from __future__ import annotations

import numpy as np


def draw_lengths(rng, spec: dict, n: int, seq_len: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    hi = min(int(spec["max"]), seq_len)
    return np.clip(np.rint(raw), int(spec["min"]), hi).astype(np.int32)


def make_batch(rng, data: dict, *, vocab_size: int, global_batch: int,
               seq_len: int) -> dict:
    shift = int(data.get("next_token_shift", 0))
    batch = {
        "tokens": rng.integers(
            0, vocab_size, size=(global_batch, seq_len + shift),
            dtype=np.int32,
        )
    }
    if data.get("lengths"):
        lengths = draw_lengths(rng, data["lengths"], global_batch, seq_len)
        mask = (np.arange(seq_len)[None, :] < lengths[:, None])
        batch["attention_mask"] = mask.astype(np.int32)
        batch["tokens"] = np.where(
            mask, batch["tokens"], np.int32(data.get("pad_id", 0))
        ).astype(np.int32)
    labels = data.get("labels")
    if labels:
        if labels["kind"] == "tokens":
            batch["labels"] = rng.integers(
                0, vocab_size, size=(global_batch, seq_len), dtype=np.int32
            )
        elif labels["kind"] == "classes":
            batch["labels"] = rng.integers(
                0, int(labels["num_labels"]), size=(global_batch,),
                dtype=np.int32,
            )
        else:
            raise ValueError(f"unknown label kind {labels['kind']!r}")
    return batch


def make_pool(data: dict, *, vocab_size: int, global_batch: int,
              seq_len: int, n_batches: int, seed: int) -> list:
    """``n_batches`` distinct global batches. The stream is keyed by
    ``(seed, 1)`` so it never coincides with the weights' key."""
    rng = np.random.default_rng([int(seed), 1])
    return [
        make_batch(rng, data, vocab_size=vocab_size,
                   global_batch=global_batch, seq_len=seq_len)
        for _ in range(n_batches)
    ]


def padding_share(pool: list) -> float:
    """Share of batch positions that hold padding (0 without a mask)."""
    if "attention_mask" not in pool[0]:
        return 0.0
    real = sum(int(b["attention_mask"].sum()) for b in pool)
    total = sum(b["attention_mask"].size for b in pool)
    return 1.0 - real / total


def cycle(pool: list):
    """The benchmark's own host iterator: the pool, round and round."""
    while True:
        yield from pool
