"""GPT-2 with 3-D parallelism (dp × sp × tp) and long-context ring
attention — capability beyond the reference (SURVEY.md §5.7: SP absent
there), built on its collective primitive set.

The mesh factors the world into data, sequence, and tensor axes; the
Megatron-style tensor-parallel blocks ride ``tp``, ring attention shards
the sequence over ``sp`` (each hop optionally computed by the Pallas
flash kernel), and gradients are fused-allreduced over ``dp``.

    python examples/jax/gpt2_3d_parallel.py --dp 1 --sp 2 --tp 2 \
        --seq-len 2048 --steps 10

CPU dry run (the same thing the driver's multichip validation does)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax/gpt2_3d_parallel.py --dp 2 --sp 2 --tp 2 \
        --seq-len 64 --d-model 64 --n-layers 2 --steps 3
"""

import argparse
import time

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.utils.compile_cache import enable_compile_cache
from horovod_tpu.parallel.transformer import (
    ParallelGPTConfig,
    make_parallel_train_step,
    shard_init,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--n-heads", type=int, default=12)
    ap.add_argument("--n-layers", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--batch-per-dp", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="> 0: Switch-MoE FFNs, experts sharded over dp "
                         "(4-D dp x sp x tp x ep)")
    args = ap.parse_args()
    enable_compile_cache()

    devs = jax.devices()
    need = args.dp * args.sp * args.tp
    if len(devs) < need:
        raise SystemExit(f"need {need} devices, have {len(devs)}")
    mesh = mesh_lib.build_mesh(
        {"dp": args.dp, "sp": args.sp, "tp": args.tp}, devices=devs[:need]
    )

    cfg = ParallelGPTConfig(
        vocab_size=args.vocab,
        max_len=args.seq_len,
        d_model=args.d_model,
        n_heads=args.n_heads,
        n_layers=args.n_layers,
        d_ff=4 * args.d_model,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32,
        remat=True,
        moe_experts=args.moe_experts,
    )
    opt = optax.adamw(3e-4)
    params, opt_state = shard_init(cfg, mesh, jax.random.PRNGKey(0), opt)
    step = make_parallel_train_step(cfg, opt, mesh)

    tokens = jnp.zeros(
        (args.dp * args.batch_per_dp, args.seq_len), jnp.int32
    )
    params, opt_state, loss = step(params, opt_state, tokens)  # compile
    print(f"compiled; initial loss {float(loss):.3f}")

    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    loss_val = float(loss)  # drain
    dt = time.perf_counter() - t0
    tok_per_sec = args.steps * tokens.size / dt
    print(
        f"{args.steps} steps in {dt:.2f}s — {tok_per_sec:,.0f} tokens/sec, "
        f"loss {loss_val:.3f}"
    )


if __name__ == "__main__":
    main()
