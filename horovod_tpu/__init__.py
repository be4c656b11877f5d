"""horovod_tpu — a TPU-native distributed deep-learning training framework.

A ground-up re-design of Horovod's capabilities (reference:
``firejq/horovod``) for TPU hardware: the data plane is XLA collectives
(``psum``/``all_gather``/``all_to_all``/``ppermute``) compiled over a
``jax.sharding.Mesh`` spanning the ICI torus, instead of NCCL/MPI rings
driven by a background negotiation thread. See SURVEY.md for the complete
component mapping.

Quick start (the reference's "wrap optimizer + broadcast + run" recipe,
``README.rst:60-61``)::

    import horovod_tpu as hvd
    import optax

    hvd.init()
    opt = hvd.DistributedOptimizer(optax.sgd(0.01 * hvd.size()))

    @hvd.spmd(in_specs=(hvd.P(), hvd.P(), hvd.P("hvd")), out_specs=(hvd.P(), hvd.P(), hvd.P()))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, hvd.allreduce(loss)
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: F401

from .utils import env as _env
from .context import (  # noqa: F401
    WORLD_AXIS,
    LOCAL_AXIS,
    CROSS_AXIS,
    HorovodTpuContext,
    init,
    shutdown,
    is_initialized,
    context,
    mesh,
    world_axes,
    size,
    rank,
    local_size,
    local_rank,
    cross_size,
    cross_rank,
    process_rank,
    process_count,
    is_homogeneous,
    mpi_built,
    nccl_built,
    gloo_built,
    ccl_built,
    ddl_built,
    xla_built,
    mpi_enabled,
    mpi_threads_supported,
    enable_overlap_scheduler,
)
from .exceptions import (  # noqa: F401
    HorovodTpuError,
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from .ops import (  # noqa: F401
    Average,
    Sum,
    Adasum,
    Min,
    Max,
    Product,
    ReduceOp,
    allreduce,
    grouped_allreduce,
    masked_allreduce,
    allgather,
    grouped_allgather,
    broadcast,
    alltoall,
    reducescatter,
    grouped_reducescatter,
    ppermute,
    barrier,
    Compression,
    fused_allreduce,
    fused_reducescatter,
    fused_allgather,
    quantized_fused_allreduce,
    quantized_fused_reducescatter,
)
from .ops.layout import (  # noqa: F401
    autotune_threshold,
    collective_compiler_options,
    overlap_compiler_options,
)
from .ops.collectives import join  # noqa: F401
from .functions import (  # noqa: F401
    broadcast_object,
    allgather_object,
    broadcast_variables,
    broadcast_parameters,
    broadcast_optimizer_state,
)
from .optimizer import (  # noqa: F401
    DistributedOptimizer,
    ShardedDistributedOptimizer,
    fused_adamw,
    reshard_opt_state,
    unshard_opt_state,
    grad,
    value_and_grad,
)
from .checkpoint import (  # noqa: F401
    all_steps,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from .data import (  # noqa: F401
    ShardedBatches,
    ShardedIndexSampler,
    prefetch_to_device,
)
from .utils.timeline import (  # noqa: F401
    start_jax_trace,
    start_timeline,
    stop_jax_trace,
    stop_timeline,
)
from . import obs  # noqa: F401  (runtime telemetry plane: hvd.obs.metrics())
from . import chaos  # noqa: F401  (fault injection: hvd.chaos.plan())
from . import serve  # noqa: F401  (elastic inference: hvd.serve.ServePool)
from . import guard  # noqa: F401  (fail-silent defense: hvd.guard.GuardConfig)

__version__ = "0.1.0"


def spmd(
    fn=None,
    *,
    in_specs: Any = None,
    out_specs: Any = None,
    mesh: Optional[Mesh] = None,
    jit: bool = True,
    donate_argnums=(),
    own_collective_layout: bool = True,
):
    """Run ``fn`` SPMD over the world mesh (sugar over ``jax.shard_map``).

    This is the TPU entry point that replaces the reference's "N copies of
    the script" execution model (``horovodrun``): one program, compiled once,
    running per-device with the world axes bound so every
    ``horovod_tpu`` collective and ``rank()``/``size()`` call resolves
    against the mesh.

    ``in_specs``/``out_specs`` default to fully replicated (``P()``).

    ``own_collective_layout`` (default True) compiles with
    :func:`collective_compiler_options` so the fusion threshold controls
    the emitted collective layout (see ``ops/layout.py``).
    """

    def deco(f):
        # (mesh, fusion threshold) -> compiled callable.  The threshold is
        # part of the key because it shapes the compiled program twice —
        # the trace-time bucket layout and the collective-combiner compiler
        # options — so changing HVDTPU_FUSION_THRESHOLD after first compile
        # must trigger a recompile, not be silently ignored per mesh.
        cache = {}

        def compiled():
            m = mesh if mesh is not None else context().mesh
            key = (m, _env.fusion_threshold_bytes())
            mapped = cache.get(key)
            if mapped is None:
                ispec = in_specs if in_specs is not None else P()
                ospec = out_specs if out_specs is not None else P()
                # check_vma=False: framework collectives (psum-based
                # broadcast, tiled all_gather, …) guarantee their own
                # replication invariants; the vma type system can't express
                # "gather output is replicated" without threading `reduced`
                # annotations through every user out_spec.
                mapped = jax.shard_map(
                    f, mesh=m, in_specs=ispec, out_specs=ospec, check_vma=False
                )
                if jit:
                    # Enforce the framework's fusion threshold on the
                    # compiled collective layout (ops/layout.py): without
                    # this, XLA's combiner merges every fusion bucket into
                    # one all-reduce and the bucket policy is inert.
                    opts = (
                        collective_compiler_options(
                            platform=m.devices.flat[0].platform
                        )
                        if own_collective_layout
                        else None
                    )
                    mapped = jax.jit(
                        mapped,
                        donate_argnums=donate_argnums,
                        compiler_options=opts or None,
                    )
                cache[key] = mapped
            return mapped

        @functools.wraps(f)
        def wrapper(*args):
            return compiled()(*args)

        if jit:
            # jax.stages.Lowered of the program a call dispatches (its
            # compiled HLO and memory analysis); nothing executes.
            wrapper.lower = lambda *args: compiled().lower(*args)

        return wrapper

    return deco(fn) if fn is not None else deco
