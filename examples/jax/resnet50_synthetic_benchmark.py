"""ResNet-50 synthetic benchmark, the reference's headline measurement
(``examples/tensorflow2/tensorflow2_synthetic_benchmark.py:25-44``):
random images, SGD, data-parallel DistributedOptimizer, prints
images/sec.  ``--fp16-allreduce`` maps to bf16 gradient compression (the
TPU-native analog of the reference's fp16 flag).

    python examples/jax/resnet50_synthetic_benchmark.py \
        --batch-size 128 --num-iters 30
"""

import argparse
import time

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models import ResNet50
from horovod_tpu.utils.compile_cache import enable_compile_cache
from jax.sharding import PartitionSpec as P


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=128,
                    help="per-chip batch size")
    ap.add_argument("--num-warmup-batches", type=int, default=5)
    ap.add_argument("--num-iters", type=int, default=30)
    ap.add_argument("--fp16-allreduce", action="store_true",
                    help="bf16 gradient compression")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--host-input", action="store_true",
                    help="stream numpy batches from the host through "
                    "hvd.prefetch_to_device (double-buffered H2D staging) "
                    "instead of reusing one device-resident batch — the "
                    "realistic input path")
    args = ap.parse_args()

    enable_compile_cache()
    hvd.init()
    n = hvd.size()
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    if args.host_input:
        # Batches stream from the host; keep only a 2-image init batch on
        # device (a full global batch would hold ~n*bs*224*224*3*2 bytes
        # of HBM the prefetched path never reads).
        images = labels = None
        init_batch = jnp.zeros(
            (2, args.image_size, args.image_size, 3), jnp.bfloat16
        )
    else:
        images = jnp.zeros(
            (n * args.batch_size, args.image_size, args.image_size, 3),
            jnp.bfloat16,
        )
        labels = jnp.zeros((n * args.batch_size,), jnp.int32)
        init_batch = images[:2]
    variables = model.init(jax.random.PRNGKey(0), init_batch, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    compression = (
        hvd.Compression.bf16 if args.fp16_allreduce else hvd.Compression.none
    )
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), compression=compression
    )
    opt_state = opt.init(params)
    wa = hvd.WORLD_AXIS

    @hvd.spmd(
        in_specs=(P(), P(), P(), P(wa), P(wa)),
        out_specs=(P(), P(), P(), P()),
        donate_argnums=(0, 1, 2),
    )
    def step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images, train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            return loss, updates["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, new_opt = opt.update(grads, opt_state, params)
        return (
            optax.apply_updates(params, updates),
            hvd.fused_allreduce(new_bs, op=hvd.Average),
            new_opt,
            hvd.allreduce(loss),
        )

    def drain(loss):
        # Unconditional device->host fetch to drain the async pipeline
        # (an assert would vanish under python -O).
        if not float(loss) >= 0:
            raise RuntimeError(f"bad loss: {float(loss)}")

    if args.host_input:
        import numpy as np

        def host_batches():
            # numpy-side bf16 (ml_dtypes): the H2D copy the prefetcher
            # overlaps is the same bytes the device step consumes.
            x = np.zeros(
                (n * args.batch_size, args.image_size, args.image_size, 3),
                jnp.bfloat16,
            )
            y = np.zeros((n * args.batch_size,), np.int32)
            while True:
                yield x, y

        it = hvd.prefetch_to_device(
            host_batches(),
            sharding=hvd.NamedSharding(hvd.mesh(), P(wa)),
        )
        batch = lambda: next(it)  # noqa: E731
    else:
        batch = lambda: (images, labels)  # noqa: E731

    for _ in range(args.num_warmup_batches):
        bx, by = batch()
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, bx, by
        )
    drain(loss)

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        bx, by = batch()
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, bx, by
        )
    drain(loss)
    dt = time.perf_counter() - t0

    img_per_sec = args.num_iters * n * args.batch_size / dt
    if hvd.rank() == 0:
        print(f"Total img/sec on {n} chip(s): {img_per_sec:.1f}")
        print(f"Img/sec per chip: {img_per_sec / n:.1f}")


if __name__ == "__main__":
    main()
