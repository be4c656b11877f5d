"""Test harness: virtual 8-device CPU mesh.

The reference's parallel test tier runs real multi-process collectives under
``horovodrun -np 2+`` (SURVEY.md §4). The TPU translation: run every
"parallel" test on a single process with 8 virtual CPU devices
(``--xla_force_host_platform_device_count=8``) and ``shard_map`` binding the
world axes — rank-parametric behavior is exercised exactly as in the
reference's rank-dependent tests (``test/parallel/common.py``).
"""

import os

# Must be set before JAX initializes its backends.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The whole test session runs on the virtual CPU platform, whatever the
# host has attached and whether or not JAX_PLATFORMS was exported.
jax.config.update("jax_platforms", "cpu")

import signal  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Per-test wall-clock alarm for the fast tier: a single hung test (a
# deadlocked collective, a wedged subprocess join) previously ate the
# whole 870 s tier-1 budget and surfaced as a driver timeout with no
# culprit named. The alarm fails the one test fast with a stack-accurate
# TimeoutError instead. Generous default (HVDTPU_TEST_TIMEOUT seconds);
# slow-tier tests (whole soaks, subprocess worlds) and tests marked
# ``no_timeout`` are exempt. SIGALRM only exists on the main thread of
# POSIX platforms — anywhere else this degrades to a no-op.
_TEST_TIMEOUT_SECS = float(os.environ.get("HVDTPU_TEST_TIMEOUT", "300"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    use_alarm = (
        _TEST_TIMEOUT_SECS > 0
        and hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
        and item.get_closest_marker("no_timeout") is None
        and item.get_closest_marker("slow") is None
    )
    if not use_alarm:
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded the {_TEST_TIMEOUT_SECS:.0f}s per-test "
            "wall-clock limit (HVDTPU_TEST_TIMEOUT; mark the test "
            "no_timeout to opt out)"
        )

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, _TEST_TIMEOUT_SECS)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


def cpu_devices(n=8):
    devs = jax.devices("cpu")
    assert len(devs) >= n, f"need {n} cpu devices, got {len(devs)}"
    return devs[:n]


@pytest.fixture
def world8():
    """Initialize an 8-worker flat world on CPU devices."""
    import horovod_tpu as hvd

    ctx = hvd.init(devices=cpu_devices(8))
    yield ctx
    hvd.shutdown()


@pytest.fixture
def world_hier():
    """2x4 hierarchical (cross, local) world on CPU devices."""
    import horovod_tpu as hvd
    from jax.sharding import Mesh

    devs = np.array(cpu_devices(8)).reshape(2, 4)
    mesh = Mesh(devs, (hvd.CROSS_AXIS, hvd.LOCAL_AXIS))
    ctx = hvd.init(
        mesh=mesh,
        world_axes=(hvd.CROSS_AXIS, hvd.LOCAL_AXIS),
        local_axes=(hvd.LOCAL_AXIS,),
        cross_axes=(hvd.CROSS_AXIS,),
    )
    yield ctx
    hvd.shutdown()


@pytest.fixture(scope="module")
def v5e_topology():
    """The described ``v5e:2x2``; the persistent compile cache is off
    meanwhile (an entry compiled for a described chip cannot be read back
    without one, and the next compile would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / cannot describe the chip here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()
