"""Microbench flash attention fwd/bwd on the chip.

Usage: ``python tools/bench_attention.py``.  Reports achieved TF/s at the
BERT-base shape using the ``4*B*H*S^2*D`` convention (x3.5 for fwd+bwd).
Today's code: not measured (the only readings predate PR 1, on another
installation).
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.ops.pallas_kernels import flash_attention  # noqa: E402
from horovod_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

B, S, H, D = 32, 512, 12, 64
ITERS = 200


def timed_loop(fn, *args):
    """Seconds per iteration of ``fn`` inside one carry-dependent
    ``fori_loop`` of ``ITERS`` iterations (one dispatch, so the host's
    per-call cost is 1/ITERS of a reading), closed by
    ``block_until_ready``; best of three."""

    @jax.jit
    def go(*a):
        def body(_, carry):
            out = fn(*carry)
            # True data dependence on out (x*0.0 gets folded; minimum
            # does not) so XLA cannot hoist the body.
            new_q = jnp.minimum(carry[0], out)
            return (new_q,) + carry[1:]

        final = lax.fori_loop(0, ITERS, body, a)
        return jnp.sum(final[0][0, 0, 0])

    jax.block_until_ready(go(*args))  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(go(*args))
        best = min(best, time.perf_counter() - t0)
    return best / ITERS


def main():
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit(
            "bench_attention times the compiled kernel; no TPU found "
            f"({jax.devices()[0].platform}) and the interpreter's time "
            "means nothing"
        )
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, H, D), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=False)

    dt = timed_loop(fwd, q, k, v)
    fl = 4 * B * H * S * S * D
    print(f"fwd: {dt*1e3:.3f} ms  {fl/dt/1e12:.1f} TF/s")

    def fwdbwd(q, k, v):
        out, grads = jax.value_and_grad(
            lambda q, k, v: flash_attention(q, k, v, causal=False)
            .astype(jnp.float32)
            .sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        return grads[0]

    dt = timed_loop(fwdbwd, q, k, v)
    print(f"fwd+bwd: {dt*1e3:.3f} ms  {3.5*fl/dt/1e12:.1f} TF/s")


if __name__ == "__main__":
    main()
