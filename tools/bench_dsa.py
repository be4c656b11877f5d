"""Kernels only, on the chip: learned sparse attention's select family by
name.

    python tools/bench_dsa.py [--iters 6] [--seq 8192] [--top-k 2048]
        [--select-blocks 512x512 ...] [--kl-blocks 256x256 ...]

Runs ONE layer's select call, masked flash forward + backward and index
loss with its gradients (``ops/dsa_kernels.py``, ``ops/pallas_kernels.py``
under ``keep=``) at the shape of the benchmark's cell: 1 x 8,192 positions,
32 query heads on 4 K/V heads of 128 with q rotated at the kernels' door,
an indexer of 16 heads of 64 on one key head keeping 2,048, bf16 operands.
Under ``jax.profiler.trace`` it prints one JSON line a variant of the two
tile pairs (``--select-blocks`` / ``--kl-blocks``, may repeat): the median
device microseconds a call of each of the five kernels (read from the
device plane's ``XLA Ops`` line), each group's share of the floor that
``benchmark/lib/flops_select_moe`` gives it on the device's peaks, and the
kept share the mask shows. Nothing here is compared with a reference: the
tier-1 tests do that on the CPU.
"""
import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

# tools/ is this script's directory
from bench_attention import kernel_us

KERNELS = ("hvd_dsa_select", "hvd_flash_fwd_select",
           "hvd_flash_bwd_dkv_select", "hvd_flash_bwd_dq_select",
           "hvd_dsa_kl")
H, H_KV, D, H_I, D_I = 32, 4, 128, 16, 64


def pair(text):
    a, b = text.split("x")
    return int(a), int(b)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--top-k", type=int, default=2048)
    ap.add_argument("--select-blocks", type=pair, action="append")
    ap.add_argument("--kl-blocks", type=pair, action="append")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark.lib import flops_select_moe as need
    from benchmark.lib.flops import roofline
    from benchmark.lib.peaks import peak_for
    from horovod_tpu.models.transformer import rotary_tables
    from horovod_tpu.ops import dsa_kernels as dsa
    from horovod_tpu.ops.pallas_kernels import (
        QRotary, flash_attention_with_lse,
    )

    s = args.seq
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    normal = lambda k, width, dt=jnp.bfloat16: jax.random.normal(  # noqa: E731
        k, (1, s, width), jnp.float32
    ).astype(dt)
    argv = [normal(keys[0], H * D), normal(keys[1], H_KV * D),
            normal(keys[2], H_KV * D), normal(keys[3], H_I * D_I),
            normal(keys[4], D_I),
            normal(keys[5], H_I, jnp.float32) * (H_I * D_I) ** -0.5]
    table = QRotary(*rotary_tables(s, D, theta=1e7), halves=True)
    peak = peak_for(jax.devices()[0].device_kind)
    z = dict(layers=1, batch=1, seq_len=s, topk=args.top_k, n_heads=H,
             n_kv_heads=H_KV, head_dim=D, index_heads=H_I,
             index_head_dim=D_I)
    floors_us = {
        name: roofline(cost["flops"], cost["bytes"], peak.bf16_flops,
                       peak.hbm_bytes_per_s)["seconds"] * 1e6
        for name, cost in (("select", need.select_cost(**z)),
                           ("flash", need.masked_flash_cost(**z)),
                           ("kl", need.kl_cost(**z)))
    }
    for sel in args.select_blocks or [(512, 512)]:
        for kl in args.kl_blocks or [(256, 256)]:
            def layer(q, k, v, q_idx, k_idx, w):
                keep, _, lse_idx = dsa.dsa_select(
                    q_idx, k_idx, w, top_k=args.top_k, block_q=sel[0],
                    block_k=sel[1],
                )

                def loss(q, k, v, q_idx, k_idx, w):
                    out, lse = flash_attention_with_lse(
                        q, k, v, causal=True, layout="bsm", n_heads=H,
                        n_kv_heads=H_KV, q_rotary=table, keep=keep,
                    )
                    return out.astype(jnp.float32).sum() + dsa.dsa_index_loss(
                        q, k, lse, q_idx, k_idx, w, keep, lse_idx, n_heads=H,
                        n_kv_heads=H_KV, block_q=kl[0], block_k=kl[1],
                    )

                grads = jax.grad(loss, argnums=tuple(range(6)))(
                    q, k, v, q_idx, k_idx, w
                )
                return grads, keep.astype(jnp.int32).sum()

            fn = jax.jit(layer)
            us = kernel_us(fn, argv, args.iters, KERNELS)
            kept = int(fn(*argv)[1])
            flash_us = sum(v for k, v in us.items() if "flash" in k)
            print(json.dumps({
                "seq": s, "top_k": args.top_k, "select_blocks": sel,
                "kl_blocks": kl, "us_per_call": us,
                "kept_share_of_causal": kept / need.causal_entries(s),
                "roofline_share": {
                    "select": floors_us["select"] / us["hvd_dsa_select"],
                    "flash": floors_us["flash"] / flash_us,
                    "kl": floors_us["kl"] / us["hvd_dsa_kl"],
                },
                "device": jax.devices()[0].device_kind,
            }), flush=True)


if __name__ == "__main__":
    main()
