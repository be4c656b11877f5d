"""Kernels only, on the chip: the delta-rule kernel family by name.

    python tools/bench_kda.py [--tree CHECKOUT] [--iters 8] [--shape NAME]
        [--heads 32] [--dk 96 --dv 192] [--gate channel|scalar|both]
        [--sub N ...] [--block-chunks N ...] [--no-recurrence]
        [--conv] [--out-norm]

Runs forward + backward of ``ops/kda_kernels.kda_attention`` alone (one
layer's call) at the shape of the benchmark's cell, ``kda-1x8192``: 1 x
8,192 positions, 32 heads of 128 key and value channels, bf16 operands,
float32 ``g`` and ``beta``, ``g`` drawn as the configuration's ``assumed``
initial values draw it (``-A softplus(.)``, ``A`` log-uniform in 1..16 a
head, the step log-uniform in 1e-3..1e-1 a channel). Under
``jax.profiler.trace`` it prints one JSON line a variant: the median device
microseconds a call of ``hvd_kda_fwd`` / ``hvd_kda_bwd`` (read from the
device plane's ``XLA Ops`` line), their sum's share of the floor that
``benchmark/lib/flops_linear_moe.kda_cost`` gives the recurrence on the
device's peaks, and the largest absolute error of the output and of the
five gradients against the ``use_kernel=False`` path (the recurrence a
position at a time in float32) on the same operands, whose own wall time
per call (host clock, forward + backward) is printed beside them.
``--conv`` prints one table of three more lines in place of those: the two
kernels as above (``row: kernels``), the two kernels given ``conv=`` taps on
operands as the projections leave them (``kernels+conv``; errors of out and
of all eight gradients against ``models.linear_moe.conv_silu`` followed by
the kernels without taps), and XLA's ``conv_silu`` of the three operands
alone, forward + backward from a given cotangent (``xla-conv_silu``: every
device operation of that program, microseconds a call): what the kernels'
door costs beside what it replaces. A tree without the argument prints
the first and the last.
``--out-norm`` (with ``--conv``: every kernel row with taps, as the cell
calls them) prints one table of four lines: the two kernels (``kernels``
or ``kernels+conv``), the two kernels given ``out_norm=`` (``...+out_norm``;
errors of out and of every gradient against the model's head-wise float32
RMS norm behind the kernels without it), XLA's gated head-wise norm alone
as ``models.linear_moe`` writes it on the recurrence path, forward +
backward from a given cotangent (``xla-gated_norm``: the reshape to heads,
the mean of squares, the scale and the gate's sigmoid; every device
operation, microseconds a call), and what XLA still does behind kernels
that normalise (``xla-gate``: ``o^ x tile(scale) x sigmoid(gate)``,
elementwise, forward + backward): what the kernels' exit costs beside what
it replaces.
``--gate scalar`` times the scalar-gate form (Gated DeltaNet: ``g`` one
log-decay a head, kernels ``hvd_gdn_fwd`` / ``hvd_gdn_bwd``; the floor is
``benchmark/lib/flops_linear_dense.gdn_cost``'s), ``--gate both`` that form
and, beside it on the same operands, the channel-wise kernels with ``g``
repeated over a head's key channels (where a head's widths are no whole
128-lane tiles, which that form refuses compiled, each head zero-padded to
the next tile: ``padded_to`` in its line, no errors printed for it). The
default is ``channel``, or ``both`` where ``--dk`` / ``--dv`` or a ``gdn-``
shape is given: ``--shape gdn-1x8192`` is ``--dk 96 --dv 192 --heads 15``,
the Olmo-Hybrid cell's. ``--conv`` / ``--out-norm`` print their tables for
the scalar form where it is asked for.
``--sub`` / ``--block-chunks`` (may repeat) time the plan's statics at other
values than the module's; ``--tree`` imports ``horovod_tpu`` and
``benchmark`` from another checkout, as ``tools/bench_attention.py`` does.
"""
import argparse
import functools
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# tools/ is this script's directory
from bench_attention import device_events, kernel_us

KERNELS = ("hvd_kda_fwd", "hvd_kda_bwd")
# the form a gate's shape chooses -> the kernels it builds
FORMS = {"channel": KERNELS, "scalar": ("hvd_gdn_fwd", "hvd_gdn_bwd")}
# name: (batch, sequence, heads, key channels, value channels)
SHAPES = {"kda-1x8192": (1, 8192, 32, 128, 128),
          "gdn-1x8192": (1, 8192, 15, 96, 192)}


def pad_heads(x, h, to):
    """``x [B, S, h d]`` with each head zero-padded to ``to`` columns."""
    b, s, width = x.shape
    heads = x.reshape(b, s, h, width // h)
    return jnp.pad(
        heads, ((0, 0), (0, 0), (0, 0), (0, to - width // h))
    ).reshape(b, s, h * to)


def channel_wise(argv, h, dk, dv):
    """The scalar-gate operands for the channel-wise kernels: ``g``
    repeated over a head's key channels, heads padded to whole 128-lane
    tiles where they are none. Returns ``(argv, padded (dk, dv) or None)``."""
    q, k, v, g, beta, w = argv
    to_k, to_v = -(-dk // 128) * 128, -(-dv // 128) * 128
    g = jnp.repeat(g, to_k, axis=-1)
    if (to_k, to_v) == (dk, dv):
        return [q, k, v, g, beta, w], None
    return [pad_heads(q, h, to_k), pad_heads(k, h, to_k),
            pad_heads(v, h, to_v), g, beta, pad_heads(w, h, to_v)], (to_k,
                                                                   to_v)


def operands(key, b, s, h, dk, dv):
    """q, k, v as a convolution's SiLU leaves them (bf16), g and beta
    (float32) at the assumed initial values, and the loss's weights."""
    keys = jax.random.split(key, 8)
    silu = lambda k, d: jax.nn.silu(  # noqa: E731
        jax.random.normal(k, (b, s, h * d), jnp.float32)
    ).astype(jnp.bfloat16)
    a = jnp.exp(jax.random.uniform(keys[3], (h, 1), minval=0.0,
                                   maxval=np.log(16.0)))
    dt = jnp.exp(jax.random.uniform(keys[4], (h, dk), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    pre = jnp.log(jnp.expm1(dt)) + 0.1 * jax.random.normal(
        keys[5], (b, s, h, dk)
    )
    g = (-a * jax.nn.softplus(pre)).reshape(b, s, h * dk)
    beta = jax.nn.sigmoid(jax.random.normal(keys[6], (b, s, h)))
    w = jax.random.normal(keys[7], (b, s, h * dv), jnp.float32)
    return [silu(keys[0], dk), silu(keys[1], dk), silu(keys[2], dv), g,
            beta, w]


NORM_EPS = 1e-5  # the configuration's eps
GRADS = ("dq", "dk", "dv", "dg", "dbeta", "dtaps_q", "dtaps_k", "dtaps_v")


def door_operands(argv, h, dk, dv):
    """q, k, v as a projection leaves them, and taps as the model draws
    them."""
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    raw = [jax.random.normal(key, x.shape, jnp.float32).astype(x.dtype)
           for key, x in zip(keys[:3], argv[:3])]
    taps = [jax.random.uniform(key, (4, h * d), jnp.float32, -0.5, 0.5)
            for key, d in zip(keys[3:], (dk, dk, dv))]
    return raw, taps


def weighted_grads(entry, n, w):
    """``(gradients in the first n operands, out)`` of ``sum(entry(...) w)``,
    jitted."""
    def loss(*a):
        out = entry(*a)
        return (out.astype(jnp.float32) * w).sum(), out
    return jax.jit(jax.grad(loss, argnums=tuple(range(n)), has_aux=True))


def errors(got, want):
    """name: [largest absolute difference, largest absolute value]."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    return {
        name: [float(jnp.max(jnp.abs(f32(a) - f32(e)))),
               float(jnp.max(jnp.abs(f32(e))))]
        for name, a, e in zip((*GRADS[:len(got[0])], "out"),
                              (*got[0], got[1]), (*want[0], want[1]))
    }


def conv_table(args, kda_kernels, argv, h, dk, dv, emit):
    """``--conv``: the kernels, the kernels that convolve, XLA's
    convolutions alone."""
    from horovod_tpu.models.linear_moe import conv_silu

    q, k, v, g, beta, w = argv
    raw, taps = door_operands(argv, h, dk, dv)
    grads = functools.partial(weighted_grads, w=w)
    attend = functools.partial(kda_kernels.kda_attention, n_heads=h,
                               use_kernel=True)
    plain = grads(attend, 5)
    emit("kernels", kernel_us(plain, (q, k, v, g, beta), args.iters,
                              args.kernels))
    if hasattr(kda_kernels, "KdaConv"):
        outside = grads(lambda q, k, v, g, beta, *t: attend(
            *(conv_silu(x, c) for x, c in zip((q, k, v), t)), g, beta), 8)
        inside = grads(lambda q, k, v, g, beta, *t: attend(
            q, k, v, g, beta, conv=kda_kernels.KdaConv(*t)), 8)
        operands = (*raw, g, beta, *taps)
        us = kernel_us(inside, operands, args.iters, args.kernels)
        emit("kernels+conv", us, abs_err_vs_conv_silu_then_kernels=errors(
            inside(*operands), outside(*operands)
        ))

    def convolutions(xs, ts, dys):
        out = [jax.vjp(conv_silu, x, t) for x, t in zip(xs, ts)]
        return [y for y, _ in out], [pull(dy) for (_, pull), dy in
                                     zip(out, dys)]
    events = device_events(jax.jit(convolutions), (raw, taps, [q, k, v]),
                           args.iters)
    emit("xla-conv_silu",
         {"every_op": sum(us for _, us in events) / args.iters})


def norm_table(args, kda_kernels, argv, h, dk, dv, emit):
    """``--out-norm``: the kernels, the kernels that normalise their exit,
    XLA's gated head-wise norm alone and the gate that stays XLA's."""
    q, k, v, g, beta, w = argv
    b, s, width = v.shape
    attend = functools.partial(kda_kernels.kda_attention, n_heads=h,
                               use_kernel=True)
    operands, call, row = (q, k, v, g, beta), attend, "kernels"
    if args.conv:
        raw, taps = door_operands(argv, h, dk, dv)
        operands, row = (*raw, g, beta, *taps), "kernels+conv"
        call = lambda *a, **kw: attend(  # noqa: E731
            *a[:5], conv=kda_kernels.KdaConv(*a[5:]), **kw
        )

    def head_norm(o):  # the model's, on the recurrence path
        heads = o.astype(jnp.float32).reshape(b, s, h, dv)
        return heads * jax.lax.rsqrt(
            jnp.mean(heads * heads, axis=-1, keepdims=True) + NORM_EPS
        )

    grads = functools.partial(weighted_grads, n=len(operands), w=w)
    emit(row, kernel_us(grads(call), operands, args.iters, args.kernels))
    inside = grads(functools.partial(call, out_norm=NORM_EPS))
    behind = grads(lambda *a: head_norm(call(*a)).reshape(b, s, width).astype(
        v.dtype
    ))
    us = kernel_us(inside, operands, args.iters, args.kernels)
    emit(row + "+out_norm", us, abs_err_vs_norm_behind_kernels=errors(
        inside(*operands), behind(*operands)
    ))

    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    gate = jax.random.normal(keys[0], (b, s, width), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(keys[1], (dv,), jnp.float32)

    def gated_norm(o, gate, scale):
        return ((head_norm(o) * scale).reshape(b, s, width)
                * jax.nn.sigmoid(gate)).astype(o.dtype)

    def gate_only(o, gate, scale):
        return (o.astype(jnp.float32) * jnp.tile(scale, h)
                * jax.nn.sigmoid(gate)).astype(o.dtype)

    dz = w.astype(v.dtype)
    for name, fn in (("xla-gated_norm", gated_norm), ("xla-gate", gate_only)):
        def both(o, gate, scale, dz, fn=fn):
            z, pull = jax.vjp(fn, o, gate, scale)
            return z, pull(dz)
        events = device_events(jax.jit(both), (v, gate, scale, dz),
                               args.iters)
        emit(name, {"every_op": sum(us for _, us in events) / args.iters})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--shape", choices=sorted(SHAPES), default="kda-1x8192")
    ap.add_argument("--heads", type=int, help="default: the shape's own")
    ap.add_argument("--dk", type=int, help="key channels a head")
    ap.add_argument("--dv", type=int, help="value channels a head")
    ap.add_argument("--gate", choices=("channel", "scalar", "both"),
                    help="the log-decay: one a key channel, one a head, or "
                         "both forms on the same operands")
    ap.add_argument("--sub", type=int, action="append",
                    help="rows a sub-block (default: the module's)")
    ap.add_argument("--block-chunks", type=int, action="append",
                    help="chunks a grid step (default: the module's)")
    ap.add_argument("--no-recurrence", action="store_true",
                    help="skip the use_kernel=False path and the errors")
    ap.add_argument("--conv", action="store_true",
                    help="the kernels with and without taps, and XLA's "
                         "conv_silu alone: one table")
    ap.add_argument("--out-norm", action="store_true",
                    help="the kernels with and without the exit norm (with "
                         "--conv: all with taps), XLA's gated norm alone "
                         "and the gate that stays: one table")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
    from benchmark.lib.flops import roofline
    from benchmark.lib.flops_linear_moe import kda_cost
    from benchmark.lib.peaks import peak_for
    from horovod_tpu.ops import kda_kernels
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            "bench_kda times the compiled kernels; no TPU found "
            f"({device.platform}) and the interpreter's time means nothing"
        )
    peak = peak_for(device.device_kind)
    b, s, h, dk, dv = SHAPES[args.shape]
    if args.gate is None:
        other = args.dk or args.dv or args.shape.startswith("gdn-")
        args.gate = "both" if other else "channel"
    h, dk, dv = args.heads or h, args.dk or dk, args.dv or dv
    argv = operands(jax.random.PRNGKey(0), b, s, h, dk, dv)
    scalar = args.gate != "channel"
    if scalar:  # one log-decay a head: each head's first channel's
        from benchmark.lib.flops_linear_dense import gdn_cost as kda_cost

        argv[3] = argv[3][..., ::dk]
    args.kernels = FORMS["scalar" if scalar else "channel"]
    cost = kda_cost(batch=b, seq_len=s, n_heads=h, d_k=dk, d_v=dv, layers=1)
    floor = roofline(cost["flops"], cost["bytes"], peak.bf16_flops,
                     peak.hbm_bytes_per_s)

    def grads(use_kernel, **statics):
        def loss(q, k, v, g, beta, w):
            out = kda_kernels.kda_attention(
                q, k, v, g, beta, n_heads=h, use_kernel=use_kernel, **statics
            )
            return (out.astype(jnp.float32) * w).sum(), out
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))

    if args.conv or args.out_norm:
        def emit(row, us, **more):
            print(json.dumps(dict(
                tree=args.tree, shape=args.shape, heads=h, row=row,
                device_kind=device.device_kind, us_per_call=us,
                total_us=sum(us.values()), **more,
            )), flush=True)
        table = norm_table if args.out_norm else conv_table
        return table(args, kda_kernels, argv, h, dk, dv, emit)

    want = seconds = None
    if not args.no_recurrence:
        plain = grads(False)
        jax.block_until_ready(plain(*argv))
        start = time.perf_counter()
        want = jax.block_until_ready(plain(*argv))
        seconds = time.perf_counter() - start
    module_chunks = kda_kernels.BLOCK_CHUNKS
    rows = [("scalar" if scalar else "channel", argv, None)]
    if args.gate == "both":
        rows.append(("channel", *channel_wise(argv, h, dk, dv)))
    for (form, operands_, padded), sub, chunks in itertools.product(
            rows, args.sub or [None],
            args.block_chunks or [module_chunks]):
        kda_kernels.BLOCK_CHUNKS = chunks
        fn = grads(True, sub=sub)
        us = kernel_us(fn, operands_, args.iters, FORMS[form])
        errors = None
        if want is not None and padded is None:
            got = fn(*operands_)
            errors = {
                name: float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - e.astype(jnp.float32)
                ))) for name, a, e in zip(
                    ("dq", "dk", "dv", "dg", "dbeta", "out"),
                    (*got[0], got[1]), (*want[0], want[1]),
                )
            }
        total = sum(us.values())
        print(json.dumps(dict(
            tree=args.tree, shape=args.shape, heads=h, dk=dk, dv=dv,
            gate=form, padded_to=padded,
            sub=sub or kda_kernels.SUB, block_chunks=chunks,
            device_kind=device.device_kind, us_per_call=us,
            total_us=total, floor_us=floor["seconds"] * 1e6,
            floor_bound=floor["bound"],
            share_of_floor_pct=100.0 * floor["seconds"] * 1e6 / total,
            abs_err_vs_recurrence=errors,
            recurrence_wall_s_per_call=seconds,
        )), flush=True)
    kda_kernels.BLOCK_CHUNKS = module_chunks


if __name__ == "__main__":
    main()
