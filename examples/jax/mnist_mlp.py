"""Minimal horovod_tpu recipe: the reference's "wrap optimizer +
broadcast + run" pattern (``examples/keras/keras_mnist.py``) in JAX.

Run single-host (all local TPU chips form the world)::

    python examples/jax/mnist_mlp.py --steps 200

Or on CPU with a virtual 8-chip world::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/jax/mnist_mlp.py --steps 50
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

import horovod_tpu as hvd
from horovod_tpu.utils.compile_cache import enable_compile_cache
from jax.sharding import PartitionSpec as P


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = x.reshape(x.shape[0], -1)
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(10)(x)


def synthetic_mnist(n=8192, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(n,)).astype(np.int32)
    # Make labels learnable: encode the label into a corner patch.
    for i in range(10):
        x[y == i, 0, i, 0] += 3.0
    return x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-per-chip", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    enable_compile_cache()
    hvd.init()
    n = hvd.size()
    model = MLP()
    x, y = synthetic_mnist()
    params = model.init(jax.random.PRNGKey(0), x[:1])

    # LR scaled by world size, reference convention (README.rst:60-61).
    opt = hvd.DistributedOptimizer(optax.adam(args.lr * n))
    opt_state = opt.init(params)

    @hvd.spmd(
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P()),
    )
    def train_step(params, opt_state, bx, by):
        def loss_fn(p):
            logits = model.apply(p, bx)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, by
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, hvd.allreduce(loss)

    bs = args.batch_per_chip * n

    # Double-buffered input prefetch: batch n+1's host slicing + H2D
    # transfer is enqueued while the device runs step n (the overlap
    # pipeline's input leg — docs/api.md "Overlap & prefetch"). The
    # sharding lands each batch pre-split over the world mesh, so the
    # step's P(WORLD_AXIS) in_specs trigger no dispatch-time reshard.
    def batches():
        for step in range(args.steps):
            i = (step * bs) % (len(x) - bs)
            yield x[i : i + bs], y[i : i + bs]

    batch_sharding = hvd.NamedSharding(hvd.mesh(), P(hvd.WORLD_AXIS))
    for step, (bx, by) in enumerate(
        hvd.prefetch_to_device(batches(), sharding=batch_sharding)
    ):
        params, opt_state, loss = train_step(params, opt_state, bx, by)
        if hvd.rank() == 0 and step % 50 == 0:
            print(f"step {step}: loss {float(loss):.4f}")
    if hvd.rank() == 0:
        print(f"final loss {float(loss):.4f}")
        assert float(loss) < 1.0


if __name__ == "__main__":
    main()
