"""Process/device context: ``init``, ``rank``, ``size`` and friends.

TPU-native re-design of the reference's basics layer
(``horovod/common/basics.py:22-252`` — ``init/shutdown/size/rank/local_rank``)
and of ``HorovodGlobalState`` (``horovod/common/global_state.h:43-132``).

Where the reference assigns one MPI rank per GPU process, the TPU-native
model is SPMD over a ``jax.sharding.Mesh``:

* A **worker** is a mesh device. ``size()`` is the number of devices in the
  world mesh; inside a sharded computation ``rank()`` is the device's index
  along the world axes (``jax.lax.axis_index``). This mirrors the reference
  rank/size semantics (rank == one accelerator) without one process per chip.
* A **process** (JAX "host") drives several local devices. Outside traced
  code ``rank()`` returns the rank of the process's first device, so the
  idiom ``if hvd.rank() == 0: checkpoint()`` keeps the reference meaning
  ("exactly one worker does this"; cf. reference examples
  ``examples/pytorch/pytorch_imagenet_resnet50.py``).
* ``local_rank``/``local_size`` and ``cross_rank``/``cross_size`` mirror the
  reference's local/cross communicators (``horovod/common/mpi/mpi_context.h:81-86``,
  ``controller.h:122-125``): *local* is intra-host (rides ICI), *cross* is
  the inter-host axis (rides DCN) in a hierarchical mesh.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh

from .exceptions import NotInitializedError

log = logging.getLogger("horovod_tpu")

# Default name of the flat data-parallel world axis.
WORLD_AXIS = "hvd"
# Hierarchical axis names (intra-host / inter-host), mirroring the
# reference's local/cross communicator split.
LOCAL_AXIS = "local"
CROSS_AXIS = "cross"


@dataclasses.dataclass(frozen=True)
class HorovodTpuContext:
    """Immutable world description; the analog of ``HorovodGlobalState``."""

    mesh: Mesh
    world_axes: Tuple[str, ...]  # mesh axes that together form the DP world
    local_axes: Tuple[str, ...]  # subset of world_axes that is intra-host
    cross_axes: Tuple[str, ...]  # subset of world_axes that is inter-host

    @property
    def world_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.world_axes]))

    @property
    def local_size(self) -> int:
        if self.local_axes:
            return int(np.prod([self.mesh.shape[a] for a in self.local_axes]))
        return max(1, jax.local_device_count())

    @property
    def cross_size(self) -> int:
        if self.cross_axes:
            return int(np.prod([self.mesh.shape[a] for a in self.cross_axes]))
        return max(1, self.world_size // self.local_size)


_lock = threading.Lock()
_context: Optional[HorovodTpuContext] = None


def init(
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    mesh: Optional[Mesh] = None,
    hierarchical: bool = False,
    world_axes: Optional[Sequence[str]] = None,
    local_axes: Sequence[str] = (),
    cross_axes: Sequence[str] = (),
) -> HorovodTpuContext:
    """Initialize the world context.

    Parity: ``hvd.init()`` (``horovod/common/operations.cc:712``,
    ``InitializeHorovodOnce`` ``:651-699``). The reference spins up a
    background thread and MPI/Gloo contexts; on TPU the data plane is XLA
    collectives inside compiled programs, so init only has to pin down the
    device mesh and rank semantics. (The dynamic-enqueue native runtime in
    ``horovod_tpu.native`` has its own explicit start.)

    Args:
      devices: devices to build a 1-D world mesh over. Defaults to
        ``jax.devices()``.
      mesh: pre-built mesh to adopt (takes precedence over ``devices``).
        ``world_axes`` selects which of its axes form the DP world
        (default: all axes).
      hierarchical: build a 2-D ``(cross, local)`` mesh — ``local`` spans
        each process's devices (ICI), ``cross`` spans processes (DCN) —
        mirroring the reference's hierarchical allreduce layout
        (``nccl_operations.cc:292-364``).
    """
    global _context
    from .obs import build as _build

    # Counts and times every trace, lowering and compile by function
    # name from here on (obs/build.py): on by default, builds are rare.
    _build.install()
    with _lock:
        if mesh is not None:
            axes = tuple(world_axes) if world_axes else tuple(mesh.axis_names)
            ctx = HorovodTpuContext(
                mesh=mesh,
                world_axes=axes,
                local_axes=tuple(local_axes),
                cross_axes=tuple(cross_axes),
            )
        else:
            if devices is not None:
                devs = list(devices)
            else:
                # The world is whatever JAX found; say so, because on a
                # host whose accelerator failed to attach that is one CPU.
                devs = list(jax.devices())
                log.info(
                    "hvd.init(): found %d %s device(s), kind %r",
                    len(devs), devs[0].platform, devs[0].device_kind,
                )
            if hierarchical:
                local = max(
                    1, len([d for d in devs if d.process_index == devs[0].process_index])
                )
                cross = len(devs) // local
                arr = np.asarray(devs).reshape(cross, local)
                ctx = HorovodTpuContext(
                    mesh=Mesh(arr, (CROSS_AXIS, LOCAL_AXIS)),
                    world_axes=(CROSS_AXIS, LOCAL_AXIS),
                    local_axes=(LOCAL_AXIS,),
                    cross_axes=(CROSS_AXIS,),
                )
            else:
                ctx = HorovodTpuContext(
                    mesh=Mesh(np.asarray(devs), (WORLD_AXIS,)),
                    world_axes=(WORLD_AXIS,),
                    local_axes=(),
                    cross_axes=(),
                )
        _context = ctx
        return ctx


def shutdown() -> None:
    """Tear down the context (parity: ``horovod_shutdown``,
    ``operations.cc:718``)."""
    global _context
    with _lock:
        _context = None


def _overlap_xla_flags(platform: str) -> Tuple[str, ...]:
    """Process-level ``XLA_FLAGS`` form of the overlap scheduler knobs —
    derived from the ONE per-platform table behind
    :func:`horovod_tpu.ops.layout.overlap_compiler_options`, so the env
    layer and the per-compile layer of ``make_train_step(overlap=True)``
    can never drift apart (TPU gets the ``xla_tpu_*`` knobs, GPU its
    ``xla_gpu_*`` twin, anything else ``()``). Some backend builds only
    honor these through XLA_FLAGS at backend init, which is why both
    layers exist. Imported lazily: ``ops`` imports this module at
    package init."""
    from .ops.layout import overlap_compiler_options

    return tuple(
        f"--{k}={v}" for k, v in overlap_compiler_options(platform).items()
    )

def enable_overlap_scheduler(platform: Optional[str] = None) -> Tuple[str, ...]:
    """Arm the XLA latency-hiding scheduler via ``XLA_FLAGS``.

    Call before the first JAX backend use (ideally before ``init()``) —
    env flags are read once at backend initialization. The flag set is
    platform-keyed (TPU gets the ``xla_tpu_*`` knobs, GPU the
    ``xla_gpu_*`` scheduler flag). Safe fallbacks:

    * On CPU test platforms (``JAX_PLATFORMS=cpu`` or an explicit
      ``platform="cpu"``) this is a no-op returning ``()`` — the CPU
      backend has no scheduler flag and would crash on unknown flags.
    * If the backend is already initialized the env write is harmless
      but inert; the per-compile options from
      :func:`~horovod_tpu.ops.layout.overlap_compiler_options` (which
      ``make_train_step(overlap=True)`` always passes) still apply.

    ``make_train_step`` does not call this (since PR 29): it passes the
    same options per compile, which every backend that knows them
    accepts. The environment form is for callers who arm the scheduler
    for a whole process before ``hvd.init()``, and it is unforgiving: a
    process whose XLA does not know one of the ``xla_tpu_*`` names dies
    at backend start-up (``parse_flags_from_env.cc: Unknown flags in
    XLA_FLAGS``; seen in ISSUE 29's session, where a process that had
    the flags written into its environment compiled for a described
    chip).

    Returns the flags appended to ``XLA_FLAGS`` (empty if none).
    """
    plat = platform or os.environ.get("JAX_PLATFORMS", "")
    # Only the PRIMARY platform decides ("tpu,cpu" — TPU with CPU
    # fallback — must still arm the flags).
    primary = plat.split(",")[0].strip().lower()
    if primary == "cpu":
        return ()
    if not primary:
        # No explicit platform: probe for a TPU runtime first, then a GPU
        # plugin — unknown xla_tpu_*/xla_gpu_* tokens in XLA_FLAGS are
        # fatal at backend init on builds lacking them, so only arm what
        # is plausibly present.
        import importlib.util
        import pkgutil

        if importlib.util.find_spec("libtpu") is not None or os.environ.get(
            "TPU_NAME"
        ):
            primary = "tpu"
        elif any(
            # Prefix scan, not a hardcoded version list: the PJRT GPU
            # plugins ship as jax_cuda<NN>_plugin / jax_rocm<NN>_plugin
            # and the version suffix moves with every CUDA/ROCm release.
            m.name.startswith(("jax_cuda", "jax_rocm"))
            for m in pkgutil.iter_modules()
        ):
            primary = "gpu"
        else:
            return ()
    existing = os.environ.get("XLA_FLAGS", "")
    # Whole-token match, not substring: --xla_tpu_enable_async_collective_
    # fusion is a prefix of its _fuse_all_gather sibling, and a user-set
    # sibling must not suppress adding the shorter flag.
    existing_names = {tok.split("=")[0] for tok in existing.split()}
    added = tuple(
        f
        for f in _overlap_xla_flags(primary)
        if f.split("=")[0] not in existing_names
    )
    if added:
        os.environ["XLA_FLAGS"] = (existing + " " + " ".join(added)).strip()
    return added


def is_initialized() -> bool:
    return _context is not None


def device_platform() -> str:
    """Platform the framework's programs run on: the world mesh's devices
    once :func:`init` has run, else JAX's default backend. The ONE place
    kernel and compile-option choices (Pallas compiled vs. interpreted,
    flash vs. XLA attention, the ``xla_tpu_*`` options) are derived from,
    so a world built over ``jax.devices("cpu")`` on a TPU host — or over a
    described TPU topology on a CPU host — picks the paths of the devices
    it will actually run on."""
    ctx = _context
    if ctx is not None:
        return ctx.mesh.devices.flat[0].platform
    return jax.default_backend()


def context() -> HorovodTpuContext:
    if _context is None:
        raise NotInitializedError()
    return _context


def mesh() -> Mesh:
    return context().mesh


def world_axes() -> Tuple[str, ...]:
    return context().world_axes


def _axis_or_world(axis) -> Tuple[str, ...]:
    """Normalize an ``axis`` argument: None → context world axes."""
    if axis is None:
        return context().world_axes
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def _in_trace(axes: Tuple[str, ...]) -> bool:
    """True when called under a trace with all ``axes`` bound (shard_map)."""
    try:
        for a in axes:
            lax.axis_size(a)
        return True
    except NameError:
        return False


def _traced_size(axes: Tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= int(lax.axis_size(a))
    return size


def size(axis=None) -> int:
    """World size (number of worker devices). Parity: ``hvd.size()``."""
    axes = _axis_or_world(axis)
    if _in_trace(axes):
        return _traced_size(axes)
    c = context()
    return int(np.prod([c.mesh.shape[a] for a in axes]))


def rank(axis=None):
    """Worker rank.

    Inside a sharded computation (``shard_map`` over the world mesh), this is
    the traced device index along the world axes. Outside, it is the rank of
    this process's first device — preserving the reference idiom
    ``hvd.rank() == 0`` for "primary worker only" work.
    """
    axes = _axis_or_world(axis)
    if _in_trace(axes):
        return lax.axis_index(axes if len(axes) > 1 else axes[0])
    c = context()
    return jax.process_index() * c.local_size


def local_size() -> int:
    """Devices on this host (parity: ``hvd.local_size()``)."""
    c = context()
    if c.local_axes and _in_trace(c.local_axes):
        return _traced_size(c.local_axes)
    return c.local_size


def local_rank():
    """Rank within this host (parity: ``hvd.local_rank()``)."""
    c = context()
    if c.local_axes and _in_trace(c.local_axes):
        la = c.local_axes if len(c.local_axes) > 1 else c.local_axes[0]
        return lax.axis_index(la)
    if _in_trace(c.world_axes):
        wa = c.world_axes if len(c.world_axes) > 1 else c.world_axes[0]
        return lax.axis_index(wa) % c.local_size
    return 0


def cross_size() -> int:
    """Number of hosts (parity: ``hvd.cross_size()``)."""
    return context().cross_size


def cross_rank():
    """This host's rank (parity: ``hvd.cross_rank()``)."""
    c = context()
    if c.cross_axes and _in_trace(c.cross_axes):
        ca = c.cross_axes if len(c.cross_axes) > 1 else c.cross_axes[0]
        return lax.axis_index(ca)
    if _in_trace(c.world_axes):
        wa = c.world_axes if len(c.world_axes) > 1 else c.world_axes[0]
        return lax.axis_index(wa) // c.local_size
    return jax.process_index()


def process_rank() -> int:
    """Explicit process-level rank (JAX process index)."""
    return jax.process_index()


def process_count() -> int:
    """Explicit process-level world size."""
    return jax.process_count()


def is_homogeneous() -> bool:
    """Parity: ``hvd.is_homogeneous()`` — same local_size on every host.

    TPU pod slices are homogeneous by construction.
    """
    return True


# Build-capability introspection, parity with horovod/common/basics.py
# (mpi_built/nccl_built/gloo_built...). The TPU framework's data plane is
# XLA collectives; none of the reference transports exist here.
def mpi_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def xla_built() -> bool:
    """The one true data plane."""
    return True


def mpi_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False
