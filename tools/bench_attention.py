"""Kernels only, on the chip: the three flash kernels by name.

    python tools/bench_attention.py [--tree CHECKOUT] [--iters 8]
        [--heads 12] [--head-dim 64] [--v-head-dim 64] [--shape NAME]
        [--block-q N --block-k N] [--kv-heads N] [--window N]
        [--rotary none|pairs|halves] [--norm] [--keep SHARE]

Runs forward + backward of ``flash_attention`` alone (one layer's worth) at
the shapes of the benchmark's flash cells — GPT-2-small (16 x 1024, causal)
and BERT-base MLM (32 x 512, non-causal), packed ``bsm`` layout, ``--heads`` heads
of ``--head-dim`` (12 of 64 as the models have them; 6 of 128 are the same
768 columns; ``--v-head-dim`` for v / out heads of another width), bf16, the
kernels' own block sizes as the models leave them — under
``jax.profiler.trace`` and prints one JSON line per shape and entry: the
median device microseconds of ``hvd_flash_fwd`` / ``hvd_flash_bwd_dkv`` /
``hvd_flash_bwd_dq`` per call, read from the device plane's ``XLA Ops``
line, and the largest absolute error of the three gradients against
float32 ``jax.numpy`` attention at batch 2 and at most 1024 positions. The
``latent`` shape is the expert cell's (2 x 4096, causal, 32 heads of 128 + 64
/ 128) and runs through both entries: ``qkv`` (``flash_attention`` on K built
with the rotary key broadcast to every head) and ``latent``
(``flash_attention_latent`` on the packed ``kv`` and the one rotary key;
skipped where ``--tree`` has none). ``window-1x16384`` is the window cell's
attention (1 x 16,384, causal, 28 query heads on 4 K/V heads of 128, window
4,096; ``--window 0`` times its full layers, ``--kv-heads 28`` a head of K/V a
query head; the error check's 1,024 positions lie inside the window, so it
checks the groups, and ``tests/test_pallas_kernels.py`` the band).
``--rotary pairs|halves`` adds, after each entry's plain row, a row
``<entry>+rotary`` of the same call given ``q_rotary`` tables (the kernels
rotate q and turn dQ back; at ``latent`` a head's last 64 lanes, elsewhere
the whole head), checked against float32 attention on q rotated by
``models.transformer.rotary``: the two rows' difference is what the turn
costs the kernels; a tree without the argument prints the plain rows only.
``--norm`` adds, after each of those rows, a row ``..+norm`` of the same call
given ``q_norm`` (the kernels norm each head of q ahead of the turn and dQ
leaves as the raw q's gradient), checked against float32 attention on q
normed as ``models.transformer.RMSNorm`` norms it: what the norm costs the
kernels is the difference to the row before. ``select-1x8192`` is the
sparse-attention cell's attention (1 x 8,192, causal, 32 query heads on 4 K/V
heads of 128); ``--keep SHARE`` hands every call an int8 ``keep`` mask that
keeps that share of the entries at random and the diagonal (the kernels are
then the ``_select`` ones; the cell keeps 0.4375 of its causal entries).
``--tree`` imports
``horovod_tpu`` from another checkout (a parent commit unpacked beside
this one), so two commits can be timed in one chip call. This is where a
kernel change is judged before a cell is run; ``benchmark/split.py`` gives
the same three names inside a whole step. Since PR 49 the rows hold the
backward's row statistic too: dQ makes ``rowsum(g * out) - g_lse`` itself
and dK/dV reads it from dQ; on an older tree XLA's einsum made it in front
of the kernels, outside the three names, so such a tree's rows leave it out.
"""
import argparse
import glob
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import ProfileData

KERNELS = ("hvd_flash_bwd_dkv", "hvd_flash_bwd_dq", "hvd_flash_fwd")
# name: (batch, sequence, causal, heads, q / k head, v / out head, of the
# q / k head the rotary columns every head of a key shares)
SHAPES = {"gpt2-16x1024-causal": (16, 1024, True, 12, 64, 64, 0),
          "bert-32x512": (32, 512, False, 12, 64, 64, 0),
          "latent": (2, 4096, True, 32, 192, 128, 64),
          "window-1x16384": (1, 16384, True, 28, 128, 128, 0),
          "select-1x8192": (1, 8192, True, 32, 128, 128, 0)}
# name: (K/V heads, window) where they are not the query heads' and none
GROUPED = {"window-1x16384": (4, 4096), "select-1x8192": (4, None)}
EPS = 1e-6


def built_keys(kv, k_rope, heads, n):
    """``(k, v)`` in the packed layout out of latent attention's operands:
    the shared rotary key broadcast to every head and set beside each
    head's own ``n`` columns, as the model did before the kernels read
    ``kv``."""
    b, s, r = k_rope.shape
    kv = kv.reshape(b, s, heads, -1)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_rope[:, :, None], (b, s, heads, r))],
        axis=-1,
    )
    return k.reshape(b, s, -1), kv[..., n:].reshape(b, s, -1)


def reference(q, k, v, w, causal, heads, kv_heads=None, window=None,
              keep=None):
    """Loss of float32 attention written out in ``jax.numpy``; ``keep``:
    the int8 mask, keys by queries."""
    b, s, _ = v.shape
    kv_heads = kv_heads or heads
    split = lambda x, n: x.astype(jnp.float32).reshape(b, s, n, -1)  # noqa: E731
    q, k, v = split(q, heads), split(k, kv_heads), split(v, kv_heads)
    k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, precision="highest"
    ) / np.sqrt(q.shape[-1])
    if causal:
        ahead = np.arange(s)[:, None] - np.arange(s)[None, :]
        valid = (ahead >= 0) & (True if not window else ahead < window)
        if keep is not None:
            valid = valid & (keep.swapaxes(1, 2) != 0)[:, None]
        scores = jnp.where(valid, scores, -jnp.inf)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
        precision="highest",
    )
    return (out.reshape(w.shape) * w).sum()


def device_events(fn, argv, iters):
    """``(name, microseconds)`` of every event on the device plane's ``XLA
    Ops`` line over ``iters`` traced calls of ``fn`` (after two to compile
    and settle)."""
    for _ in range(2):
        jax.block_until_ready(fn(*argv))
    trace_dir = tempfile.mkdtemp(prefix="flash_trace")
    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            out = fn(*argv)
        jax.block_until_ready(out)
    return [
        (ev.name, ev.duration_ns / 1e3)
        for path in glob.glob(
            os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/device:TPU:0")
        for line in plane.lines if line.name == "XLA Ops"
        for ev in line.events
    ]


def kernel_us(fn, argv, iters, kernels=KERNELS):
    """Median device microseconds per call of each kernel named in
    ``kernels`` (substrings of the trace's names)."""
    durations = {name: [] for name in kernels}
    for event, us in device_events(fn, argv, iters):
        name = next((k for k in kernels if k in event), None)
        if name:
            durations[name].append(us)
    if not all(durations.values()):
        raise SystemExit(f"kernels missing from the trace: {durations}")
    return {name: float(np.median(v)) for name, v in durations.items()}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--heads", type=int, help="default: the shape's own")
    ap.add_argument("--head-dim", type=int, help="q / k head width")
    ap.add_argument("--v-head-dim", type=int, help="v / out head width")
    ap.add_argument("--shape", choices=sorted(SHAPES), action="append",
                    help="only this shape (may repeat); default: all")
    ap.add_argument("--block-q", type=int)
    ap.add_argument("--block-k", type=int)
    ap.add_argument("--kv-heads", type=int,
                    help="K/V heads that the query heads share")
    ap.add_argument("--window", type=int, help="0: none")
    ap.add_argument("--rotary", choices=("none", "pairs", "halves"),
                    default="none", help="also time each entry with q_rotary")
    ap.add_argument("--norm", action="store_true",
                    help="also time each of those rows with q_norm")
    ap.add_argument("--keep", type=float,
                    help="share of the entries a random keep mask keeps")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
    from horovod_tpu.ops import pallas_kernels
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            "bench_attention times the compiled kernels; no TPU found "
            f"({device.platform}) and the interpreter's time means nothing"
        )
    for shape in args.shape or SHAPES:
        b, s, causal, heads, d, dv, rope = SHAPES[shape]
        heads, d = args.heads or heads, args.head_dim or d
        dv = args.v_head_dim or dv
        kv_heads, window = GROUPED.get(shape, (heads, None))
        kv_heads = args.kv_heads or kv_heads
        window = (args.window or None) if args.window is not None else window
        blocks = {}
        if args.block_q:
            blocks["block_q"] = args.block_q
        if args.block_k:
            blocks["block_k"] = args.block_k
        if kv_heads != heads:  # a tree before PR 40 takes neither
            blocks["n_kv_heads"] = kv_heads
        if window:
            blocks["window"] = window
        keep = None
        if args.keep is not None:
            keep = (jax.random.uniform(jax.random.PRNGKey(1), (b, s, s))
                    < args.keep) | jnp.eye(s, dtype=bool)
            keep = keep.astype(jnp.int8)

        def kept(length):
            """The mask's keyword for operands of ``length`` positions."""
            return {} if keep is None else dict(
                keep=keep[:2, :length, :length]
            )

        def qkv(q, k, v, **door):
            return pallas_kernels.flash_attention(
                q, k, v, causal=causal, layout="bsm", n_heads=heads,
                **blocks, **kept(q.shape[1]), **door
            )

        def latent(q, kv, k_rope, **door):
            return pallas_kernels.flash_attention_latent(
                q, kv, k_rope, causal=causal, n_heads=heads, **blocks,
                **door
            )[0]

        from horovod_tpu.models import transformer

        # the rotated lanes of a q head: its last ``rope``, or all of it
        turned = rope or d
        halves = args.rotary == "halves"
        scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (d,))

        def rotary_tables(length):
            return dict(q_rotary=pallas_kernels.QRotary(
                *transformer.rotary_tables(length, turned, theta=1e6),
                halves=halves, start=d - turned,
            ))

        def rotated(q):
            """q as ``rotary`` turns it, for the float32 loss."""
            q4 = q.astype(jnp.float32).reshape(*q.shape[:2], heads, d)
            return jnp.concatenate([
                q4[..., :d - turned], transformer.rotary(
                    q4[..., d - turned:], theta=1e6, halves=halves
                ),
            ], axis=-1).reshape(q.shape)

        def normed(q):
            """q as ``RMSNorm`` norms its heads, for the float32 loss."""
            q4 = q.astype(jnp.float32).reshape(*q.shape[:2], heads, d)
            q4 = q4 * jax.lax.rsqrt(
                jnp.mean(q4 * q4, axis=-1, keepdims=True) + EPS
            ) * scale
            return q4.reshape(q.shape)

        # (the row's suffix, the entry's keywords for a length, q as the
        # float32 loss takes it)
        variants = [("", lambda length: {}, lambda q: q)]
        if args.rotary != "none" and hasattr(pallas_kernels, "QRotary"):
            variants.append(("+rotary", rotary_tables, rotated))
        if args.norm and hasattr(pallas_kernels, "QNorm"):
            variants = [row for suffix, door, seen in variants for row in (
                (suffix, door, seen),
                (suffix + "+norm", lambda length, door=door: dict(
                    door(length),
                    q_norm=pallas_kernels.QNorm(scale, EPS),
                ), lambda q, seen=seen: seen(normed(q))),
            )]

        def exact(q, k, v, w):
            return reference(q, k, v, w, causal, heads, kv_heads, window,
                             kept(q.shape[1]).get("keep"))

        # entry: (the kernels' call, operand widths, float32 loss)
        entries = {"qkv": (
            qkv, (heads * d, kv_heads * d, kv_heads * dv), exact
        )}
        if rope and hasattr(pallas_kernels, "flash_attention_latent"):
            entries["latent"] = (
                latent, (heads * d, heads * (d - rope + dv), rope),
                lambda q, kv, k_rope, w: exact(
                    q, *built_keys(kv, k_rope, heads, d - rope), w
                ),
            )
        for (entry, (call, widths, exact_loss)), (suffix, door, seen) in (
            (e, v) for e in entries.items() for v in variants
            if e[0] == "qkv" or "+norm" not in v[0]  # no latent q_norm
        ):
            keys = jax.random.split(jax.random.PRNGKey(0), 4)
            argv = [
                jax.random.normal(key, (b, s, width), jnp.float32)
                for key, width in zip(keys, widths + (heads * dv,))
            ]
            argv = [x.astype(jnp.bfloat16) for x in argv[:3]] + argv[3:]

            def loss(*operands, call=call, door=door):
                out = call(*operands[:3], **door(operands[0].shape[1]))
                return (out.astype(jnp.float32) * operands[3]).sum()

            exact_loss = (lambda q, *rest, f=exact_loss, seen=seen:  # noqa: E731
                          f(seen(q), *rest))
            flash = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            us = kernel_us(flash, argv, args.iters)
            small = [x[:2, :1024] for x in argv]
            want = jax.jit(jax.grad(exact_loss, argnums=(0, 1, 2)))(*small)
            errors = [
                float(jnp.max(jnp.abs(a.astype(jnp.float32) - e)))
                for a, e in zip(flash(*small), want)
            ]
            print(json.dumps(dict(
                tree=args.tree, shape=shape, entry=entry + suffix,
                heads=heads, head_dim=d, v_head_dim=dv, blocks=blocks,
                keep=args.keep,
                device_kind=device.device_kind, us_per_call=us,
                total_us=sum(us.values()), grad_abs_err_vs_f32=errors,
            )), flush=True)


if __name__ == "__main__":
    main()
