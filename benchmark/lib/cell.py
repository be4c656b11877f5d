"""A cell built and warmed up as ``run.py`` builds it, for the chip tools
that measure something else than the result line (``split.py``,
``planes_cost.py``): no reference, no window."""

from __future__ import annotations

import dataclasses

from . import compile_info, data as data_lib, harness, resolve


@dataclasses.dataclass
class Built:
    cell: harness.Cell
    devices: list
    hvd: object  # the program under test, imported after the devices
    step: object
    state: object
    batches: object  # hvd.prefetch_to_device over the cell's cycled pool
    counter: compile_info.CompileCounter
    cache_dir: str


def build(root: str, bench_dir: str, workload: str, *, seed: int,
          tiny: bool) -> Built:
    """``tiny`` is a rehearsal on the CPU at the files' ``tiny`` sizes."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    cell = harness.load_cell(root, bench_dir, workload)
    if tiny:
        cell = harness.tiny(cell)
    devices, _ = harness.pick_devices(jax, cell.chips, rehearsal=tiny)

    import horovod_tpu as hvd
    from horovod_tpu.parallel import dp
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = compile_info.CompileCounter()
    hvd.init(devices)
    traffic = cell.traffic
    family = resolve.load_family(bench_dir, traffic["family"]).build(
        cell.config, traffic
    )
    step, wrapped, _ = harness.build_step(cell, family, hvd, dp, optax)
    state = dp.init_state(
        family.init_params(jax.random.PRNGKey(seed)), wrapped
    )
    pool = data_lib.make_pool(
        traffic["data"], vocab_size=family.vocab_size,
        global_batch=traffic["per_chip_batch"] * cell.chips,
        seq_len=traffic["seq_len"], n_batches=traffic["pool_batches"],
        seed=seed,
    )
    batches = hvd.prefetch_to_device(
        data_lib.cycle(pool),
        sharding=NamedSharding(hvd.mesh(), P(hvd.WORLD_AXIS)),
    )
    return Built(cell, devices, hvd, step, state, batches, counter,
                 cache_dir)


def warm_up(built: Built, batch=None) -> None:
    """Steps until ``harness.QUIET_STEPS`` in a row compile nothing;
    ``batch`` is the first one, where the caller already took it."""
    quiet = 0
    for _ in range(harness.WARMUP_MAX_STEPS):
        if batch is None:
            batch = next(built.batches)
        built.state, loss = built.step(built.state, batch)
        loss.block_until_ready()
        batch = None
        compiled = built.counter.take()["compile_requests"]
        quiet = quiet + 1 if compiled == 0 else 0
        if quiet >= harness.QUIET_STEPS:
            return
    raise RuntimeError(
        f"still compiling after {harness.WARMUP_MAX_STEPS} steps"
    )
