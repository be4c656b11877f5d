"""``hvdtpu-run`` CLI — the ``horovodrun`` equivalent.

Parity: ``horovod/runner/launch.py`` (arg surface ``:247-438``,
``_run_static:527``, ``_run_elastic:619``, ``run_commandline:761``).
Static jobs parse ``-H host1:4,host2:4`` (or discover the pod slice from
the TPU env) and fan out one controller process per host; elastic jobs
poll a discovery script and drive restarts through the elastic driver.

Config knobs mirror the reference's flag→env convention
(``horovod/runner/common/util/config_parser.py``): every ``--fusion-*``/
``--timeline-*``/``--autotune*`` flag becomes an ``HVDTPU_*`` env var read
by :mod:`horovod_tpu.utils.env`.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List

from . import api
from .hosts import discover_tpu_hosts, parse_hosts


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdtpu-run",
        description="Launch a horovod_tpu training job across TPU hosts.",
    )
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="total worker (chip) count; default: all discovered")
    p.add_argument("-H", "--hosts", default=None,
                   help="comma-separated host:slots list")
    p.add_argument("--hostfile", default=None,
                   help="file with one host:slots per line")
    p.add_argument("--verbose", "-v", action="store_true")
    # Elastic (parity: --min-np/--max-np/--host-discovery-script).
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None)
    p.add_argument("--reset-limit", type=int, default=None)
    # Control-plane high availability: durable KV/driver journal and
    # the crash-adoption restart path (see docs/elastic.md).
    p.add_argument("--journal-dir", default=None,
                   help="directory for the durable control-plane journal "
                        "(HVDTPU_JOURNAL_DIR)")
    p.add_argument("--adopt", action="store_true",
                   help="adopt a crashed/preempted driver's journaled state "
                        "and its still-running workers (needs --journal-dir)")
    # Perf knobs → env (config_parser.py convention).
    p.add_argument("--fusion-threshold-mb", type=int, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--no-stall-check", action="store_true")
    p.add_argument("--stall-warning-time-seconds", type=float, default=None)
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--network-interface", "--nics", dest="network_interface",
                   default=None,
                   help="NIC name to advertise/bind rendezvous and peer-mesh "
                        "links on (multi-homed hosts). Sets HVDTPU_IFACE. "
                        "Parity: reference --network-interface(s).")
    p.add_argument("--log-level", default=None,
                   choices=["trace", "debug", "info", "warning", "error"],
                   help="native runtime log level (reference --log-level)")
    p.add_argument("--start-timeout", type=int, default=None,
                   help="seconds workers may take to form the world "
                        "(reference --start-timeout)")
    p.add_argument("--output-filename", default=None,
                   help="redirect worker output to "
                        "<dir>/rank.<N>/stdout|stderr (reference layout)")
    p.add_argument("--config-file", default=None,
                   help="YAML config (reference --config-file schema); "
                        "explicit CLI flags win over file values")
    p.add_argument("-cb", "--check-build", action="store_true",
                   help="print available frameworks/controllers/"
                        "operations and exit (reference --check-build)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command to run")
    return p


def check_build() -> str:
    """Capability report (parity: ``horovodrun --check-build``,
    reference ``launch.py:110-147``). Frameworks probe importability;
    controllers/operations reflect this build's actual planes."""
    import importlib.util

    from .. import __version__

    def mark(avail: bool) -> str:
        return "X" if avail else " "

    def has(mod: str) -> bool:
        return importlib.util.find_spec(mod) is not None

    native_ok = True
    try:  # the C++ runtime builds lazily; surface a broken toolchain here
        from .. import native as _native

        _native.build()
    except Exception:
        native_ok = False

    return f"""\
horovod_tpu v{__version__}:

Available Frameworks:
    [{mark(has('jax'))}] JAX
    [{mark(has('tensorflow'))}] TensorFlow
    [{mark(has('torch'))}] PyTorch
    [{mark(has('keras'))}] Keras
    [{mark(has('mxnet'))}] MXNet

Available Controllers:
    [{mark(native_ok)}] native TCP (coordinator + ring data plane)
    [{mark(native_ok)}] same-host shared-memory data plane (csrc/shm.cc)
    [{mark(has('jax'))}] XLA/SPMD (compiled collectives)

Available Tensor Operations:
    [{mark(has('jax'))}] XLA collectives over ICI (psum/all_gather/...)
    [{mark(native_ok)}] CPU ring (reduce-scatter/allgather over TCP)
    [{mark(has('ray'))}] Ray integration
    [{mark(has('pyspark'))}] Spark integration"""


def _args_to_env(args) -> Dict[str, str]:
    """Flag → HVDTPU_* env mapping (reference config_parser.py)."""
    env: Dict[str, str] = {}
    if args.fusion_threshold_mb is not None:
        env["HVDTPU_FUSION_THRESHOLD"] = str(args.fusion_threshold_mb * 1024 * 1024)
    if args.cycle_time_ms is not None:
        env["HVDTPU_CYCLE_TIME"] = str(args.cycle_time_ms)
    if args.cache_capacity is not None:
        env["HVDTPU_CACHE_CAPACITY"] = str(args.cache_capacity)
    if args.timeline_filename:
        env["HVDTPU_TIMELINE"] = args.timeline_filename
    if args.timeline_mark_cycles:
        env["HVDTPU_TIMELINE_MARK_CYCLES"] = "1"
    if args.no_stall_check:
        env["HVDTPU_STALL_CHECK_DISABLE"] = "1"
    if args.stall_warning_time_seconds is not None:
        env["HVDTPU_STALL_CHECK_TIME_SECONDS"] = str(
            args.stall_warning_time_seconds
        )
    if args.autotune:
        env["HVDTPU_AUTOTUNE"] = "1"
    if args.autotune_log_file:
        env["HVDTPU_AUTOTUNE_LOG"] = args.autotune_log_file
    if args.network_interface:
        env["HVDTPU_IFACE"] = args.network_interface
    if args.start_timeout is not None:
        env["HVT_INIT_TIMEOUT_SECONDS"] = str(args.start_timeout)
    if args.log_level:
        env["HVT_LOG_LEVEL"] = args.log_level
    return env


def _resolve_hosts(args):
    if args.hosts:
        return parse_hosts(args.hosts)
    if args.hostfile:
        with open(args.hostfile) as f:
            return parse_hosts(",".join(l.strip() for l in f if l.strip()))
    return discover_tpu_hosts(default_slots=args.num_proc)


def run_commandline(argv: List[str] = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.check_build:
        print(check_build())
        return 0
    if args.config_file is not None:
        from .config_parser import apply_config_file

        apply_config_file(args, parser)
    command = list(args.command)
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        print("hvdtpu-run: no command given", file=sys.stderr)
        return 2

    env = _args_to_env(args)
    elastic = bool(
        args.host_discovery_script or args.min_np or args.max_np or args.adopt
    )
    if elastic:
        from .elastic_driver import run_elastic

        return run_elastic(
            command,
            discovery_script=args.host_discovery_script,
            min_np=args.min_np or 1,
            max_np=args.max_np,
            reset_limit=args.reset_limit,
            extra_env=env,
            verbose=args.verbose,
            output_dir=args.output_filename,
            journal_dir=args.journal_dir,
            adopt=args.adopt,
        )

    try:
        hosts = _resolve_hosts(args)
    except ValueError as e:
        print(f"hvdtpu-run: {e}", file=sys.stderr)
        return 2
    if args.num_proc:
        # Trim the host list to cover the requested worker count.
        total, kept = 0, []
        for h in hosts:
            if total >= args.num_proc:
                break
            kept.append(h)
            total += h.slots
        if total < args.num_proc:
            print(
                f"hvdtpu-run: requested -np {args.num_proc} but hosts "
                f"provide {total} slots",
                file=sys.stderr,
            )
            return 2
        hosts = kept
    if args.verbose:
        print(f"hvdtpu-run: hosts={[(h.hostname, h.slots) for h in hosts]}")
    return api.launch_job(
        command, hosts, extra_env=env, output_dir=args.output_filename
    )


def main() -> None:
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
