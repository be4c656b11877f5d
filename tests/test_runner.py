"""Launcher/runner tests — the reference's "single" tier
(``test/single/test_run.py``: arg parsing, host parsing, assignment;
``test_elastic_driver.py``: scripted discovery without a cluster)."""

import json
import os
import socket
import subprocess
import sys
import time
from unittest import mock

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from horovod_tpu.runner import api
from horovod_tpu.runner.elastic_driver import (
    ElasticDriver,
    FixedHosts,
    HostDiscoveryScript,
    HostManager,
    run_elastic,
)
from horovod_tpu.runner.hosts import (
    HostInfo,
    get_host_assignments,
    parse_hosts,
)
from horovod_tpu.runner.http_server import RendezvousClient, RendezvousServer
from horovod_tpu.runner.launch import build_parser, run_commandline


def test_parse_hosts():
    hosts = parse_hosts("a:4,b:2, c")
    assert [(h.hostname, h.slots) for h in hosts] == [("a", 4), ("b", 2), ("c", 1)]


def test_host_assignments_ranks():
    hosts = parse_hosts("a:2,b:2")
    slots = get_host_assignments(hosts, min_np=4)
    assert [(s.rank, s.hostname, s.local_rank, s.cross_rank) for s in slots] == [
        (0, "a", 0, 0),
        (1, "a", 1, 0),
        (2, "b", 0, 1),
        (3, "b", 1, 1),
    ]
    assert all(s.size == 4 for s in slots)
    assert all(s.cross_size == 2 for s in slots)


def test_host_assignments_min_np_error():
    with pytest.raises(ValueError):
        get_host_assignments(parse_hosts("a:2"), min_np=4)


def test_rendezvous_kv_roundtrip():
    server = RendezvousServer("127.0.0.1")
    port = server.start()
    try:
        client = RendezvousClient("127.0.0.1", port, timeout=5)
        assert client.get("scope", "missing") is None
        client.put("scope", "k1", b"hello")
        assert client.get("scope", "k1") == b"hello"
        assert client.keys("scope") == ["k1"]
        client.put("scope", "k2", b"x" * 10000)
        assert len(client.get("scope", "k2")) == 10000
    finally:
        server.stop()


def test_rendezvous_publishes_slots():
    server = RendezvousServer("127.0.0.1")
    port = server.start()
    try:
        slots = get_host_assignments(parse_hosts("a:2,b:2"), min_np=4)
        server.init(slots)
        client = RendezvousClient("127.0.0.1", port, timeout=5)
        assert client.get("rank", "0") == b"0:0:0:4:2:2"
        assert client.get("rank", "3") == b"3:1:1:4:2:2"
    finally:
        server.stop()


def test_launch_job_local_success(tmp_path):
    marker = tmp_path / "ran.txt"
    rc = api.launch_job(
        [sys.executable, "-c",
         f"import os; open(r'{marker}','w').write(os.environ['HVDTPU_PROCESS_ID'])"],
        [HostInfo("localhost", 1)],
    )
    assert rc == 0
    assert marker.read_text() == "0"


def test_launch_job_failure_propagates():
    rc = api.launch_job(
        [sys.executable, "-c", "import sys; sys.exit(3)"],
        [HostInfo("localhost", 1)],
    )
    assert rc == 3


def test_launch_job_env_injection(tmp_path):
    out = tmp_path / "env.txt"
    rc = api.launch_job(
        [sys.executable, "-c",
         "import os; open(r'%s','w').write("
         "os.environ['HVDTPU_RENDEZVOUS_PORT']+' '+"
         "os.environ['HVDTPU_NUM_PROCESSES']+' '+os.environ['X_EXTRA'])" % out],
        [HostInfo("localhost", 1)],
        extra_env={"X_EXTRA": "42"},
    )
    assert rc == 0
    port, nproc, extra = out.read_text().split()
    assert int(port) > 0 and nproc == "1" and extra == "42"


def test_cli_parser_flags_to_env():
    from horovod_tpu.runner.launch import _args_to_env

    args = build_parser().parse_args(
        [
            "--fusion-threshold-mb", "64", "--cycle-time-ms", "2.5",
            "--timeline-filename", "/tmp/t.json", "--autotune",
            "--no-stall-check", "--", "python", "train.py",
        ]
    )
    env = _args_to_env(args)
    assert env["HVDTPU_FUSION_THRESHOLD"] == str(64 * 1024 * 1024)
    assert env["HVDTPU_CYCLE_TIME"] == "2.5"
    assert env["HVDTPU_TIMELINE"] == "/tmp/t.json"
    assert env["HVDTPU_AUTOTUNE"] == "1"
    assert env["HVDTPU_STALL_CHECK_DISABLE"] == "1"
    assert args.command[1:] == ["python", "train.py"]


def test_iface_override(monkeypatch):
    """HVDTPU_IFACE routes _local_addr through the named NIC (VERDICT r2
    #9; reference probes NICs in runner/driver/driver_service.py:122-257).
    'lo' exists on any Linux box and carries 127.0.0.1, so the override
    is observable against the usual non-loopback fallbacks."""
    monkeypatch.delenv("HVDTPU_LOCAL_ADDR", raising=False)
    monkeypatch.setenv("HVDTPU_IFACE", "lo")
    assert api._local_addr() == "127.0.0.1"
    monkeypatch.setenv("HVDTPU_IFACE", "no-such-nic0")
    with pytest.raises(RuntimeError, match="no-such-nic0"):
        api._local_addr()
    # explicit address override still wins over the interface pick
    monkeypatch.setenv("HVDTPU_LOCAL_ADDR", "10.1.2.3")
    assert api._local_addr() == "10.1.2.3"


def test_cli_network_interface_flag_to_env():
    from horovod_tpu.runner.launch import _args_to_env

    args = build_parser().parse_args(
        ["--network-interface", "ens3", "--", "python", "train.py"]
    )
    assert _args_to_env(args)["HVDTPU_IFACE"] == "ens3"


def test_cli_no_command_errors():
    assert run_commandline([]) == 2


def test_cli_static_local_run(tmp_path):
    marker = tmp_path / "cli.txt"
    rc = run_commandline(
        ["-H", "localhost:1", "--",
         sys.executable, "-c", f"open(r'{marker}','w').write('ok')"]
    )
    assert rc == 0
    assert marker.read_text() == "ok"


# ---- elastic driver (reference test_elastic_driver.py patterns) ----


def test_host_manager_blacklist():
    disc = FixedHosts({"a": 2, "b": 2})
    mgr = HostManager(disc)
    mgr.update_available_hosts()
    assert mgr.current_hosts == {"a": 2, "b": 2}
    mgr.blacklist("a")
    mgr.update_available_hosts()
    assert mgr.current_hosts == {"b": 2}
    assert mgr.is_blacklisted("a")


def test_host_manager_change_detection():
    disc = FixedHosts({"a": 2})
    mgr = HostManager(disc)
    assert mgr.update_available_hosts() is True
    assert mgr.update_available_hosts() is False
    disc.set({"a": 2, "b": 2})
    assert mgr.update_available_hosts() is True


def test_discovery_script(tmp_path):
    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\necho host-a:4\necho host-b:4\n")
    script.chmod(0o755)
    disc = HostDiscoveryScript(str(script))
    assert disc.find_available_hosts_and_slots() == {"host-a": 4, "host-b": 4}


@mock.patch(
    "horovod_tpu.runner.elastic_driver.DISCOVER_HOSTS_FREQUENCY_SECS", 0.01
)
def test_elastic_driver_membership_updates():
    disc = FixedHosts({"a": 2})
    driver = ElasticDriver(disc, min_np=1)
    driver.start()
    try:
        hosts = driver.wait_for_available_slots(1, timeout=5)
        assert hosts == {"a": 2}
        disc.set({"a": 2, "b": 2})
        hosts = driver.wait_for_available_slots(4, timeout=5)
        assert hosts == {"a": 2, "b": 2}
    finally:
        driver.stop()


@mock.patch(
    "horovod_tpu.runner.elastic_driver.DISCOVER_HOSTS_FREQUENCY_SECS", 0.01
)
def test_run_elastic_retries_then_succeeds():
    calls = []

    def fake_launcher(command, hosts, extra_env=None):
        calls.append([h.hostname for h in hosts])
        return 1 if len(calls) < 3 else 0

    rc = run_elastic(
        ["train"],
        discovery=FixedHosts({"a": 1}),
        min_np=1,
        reset_limit=10,
        launcher=fake_launcher,
    )
    assert rc == 0
    assert len(calls) == 3


@mock.patch(
    "horovod_tpu.runner.elastic_driver.DISCOVER_HOSTS_FREQUENCY_SECS", 0.01
)
def test_run_elastic_reset_limit():
    rc = run_elastic(
        ["train"],
        discovery=FixedHosts({"a": 1}),
        min_np=1,
        reset_limit=2,
        launcher=lambda c, h, extra_env=None: 7,
    )
    assert rc == 7


def test_host_assignments_heterogeneous_cross_rank():
    # Review regression: cross_rank must index among hosts owning the same
    # local slot, not the absolute host index.
    slots = get_host_assignments(parse_hosts("a:1,b:2"), min_np=3)
    by = {(s.hostname, s.local_rank): s for s in slots}
    assert by[("b", 1)].cross_rank == 0
    assert by[("b", 1)].cross_size == 1
    assert by[("a", 0)].cross_rank == 0
    assert by[("b", 0)].cross_rank == 1
    assert by[("b", 0)].cross_size == 2


class TestConfigFile:
    def _write(self, tmp_path, text):
        p = tmp_path / "cfg.yaml"
        p.write_text(text)
        return str(p)

    def test_sections_map_to_args(self, tmp_path):
        from horovod_tpu.runner.config_parser import read_config_file

        path = self._write(
            tmp_path,
            """
            verbose: true
            num-proc: 8
            params:
              fusion-threshold-mb: 64
              cycle-time-ms: 2.5
            autotune:
              enabled: true
              log-file: at.csv
            timeline:
              filename: tl.json
              mark-cycles: true
            stall-check:
              enabled: false
              warning-time-seconds: 120
            elastic:
              min-np: 2
              max-np: 8
            """,
        )
        v = read_config_file(path)
        assert v["verbose"] is True
        assert v["num_proc"] == 8
        assert v["fusion_threshold_mb"] == 64
        assert v["cycle_time_ms"] == 2.5
        assert v["autotune"] is True
        assert v["autotune_log_file"] == "at.csv"
        assert v["timeline_filename"] == "tl.json"
        assert v["timeline_mark_cycles"] is True
        assert v["no_stall_check"] is True
        assert v["stall_warning_time_seconds"] == 120
        assert (v["min_np"], v["max_np"]) == (2, 8)

    def test_cli_flags_win_over_file(self, tmp_path):
        from horovod_tpu.runner.launch import build_parser
        from horovod_tpu.runner.config_parser import apply_config_file

        path = self._write(
            tmp_path,
            "params:\n  fusion-threshold-mb: 64\n  cycle-time-ms: 2.5\n",
        )
        parser = build_parser()
        args = parser.parse_args(
            ["--config-file", path, "--fusion-threshold-mb", "128", "x"]
        )
        apply_config_file(args, parser)
        assert args.fusion_threshold_mb == 128  # explicit flag wins
        assert args.cycle_time_ms == 2.5        # file fills the rest

    def test_non_mapping_rejected(self, tmp_path):
        from horovod_tpu.runner.config_parser import read_config_file

        path = self._write(tmp_path, "- just\n- a\n- list\n")
        with pytest.raises(ValueError, match="mapping"):
            read_config_file(path)

    def test_unknown_keys_rejected(self, tmp_path):
        from horovod_tpu.runner.config_parser import read_config_file

        path = self._write(
            tmp_path, "params:\n  fusion-threshold: 64\nmin-np: 2\n"
        )
        with pytest.raises(ValueError, match="fusion-threshold"):
            read_config_file(path)

    def test_quoted_numbers_coerced(self, tmp_path):
        from horovod_tpu.runner.launch import build_parser
        from horovod_tpu.runner.config_parser import apply_config_file

        path = self._write(
            tmp_path,
            'num-proc: "8"\nparams:\n  fusion-threshold-mb: "64"\n',
        )
        parser = build_parser()
        args = parser.parse_args(["--config-file", path, "x"])
        apply_config_file(args, parser)
        assert args.num_proc == 8
        assert args.fusion_threshold_mb == 64

    def test_empty_section_tolerated(self, tmp_path):
        from horovod_tpu.runner.config_parser import read_config_file

        path = self._write(tmp_path, "params:\nverbose: true\n")
        v = read_config_file(path)
        assert v["verbose"] is True


# ---- elastic worker-notification + failure attribution ----


def test_launch_job_reports_failed_host():
    from horovod_tpu.runner.api import launch_job
    from horovod_tpu.runner.hosts import HostInfo

    failed = []
    rc = launch_job(
        [sys.executable, "-c", "import sys; sys.exit(3)"],
        [HostInfo("localhost", 1)],
        on_host_failure=failed.append,
    )
    assert rc == 3
    assert failed == ["localhost"]


@mock.patch(
    "horovod_tpu.runner.elastic_driver.DISCOVER_HOSTS_FREQUENCY_SECS", 0.01
)
def test_run_elastic_blacklists_failed_host():
    """The legacy relaunch loop blacklists hosts whose processes failed
    (reference ``runner/elastic/driver.py:292-308`` attribution)."""
    disc = FixedHosts({"bad-host": 1, "good-host": 1})
    seen_worlds = []

    def fake_launcher(command, hosts, extra_env=None, on_host_failure=None):
        names = sorted(h.hostname for h in hosts)
        seen_worlds.append(names)
        if "bad-host" in names:
            on_host_failure("bad-host")
            return 1
        return 0

    rc = run_elastic(
        ["train"],
        discovery=disc,
        min_np=1,
        reset_limit=10,
        launcher=fake_launcher,
    )
    assert rc == 0
    # First world contained the bad host; the relaunch excluded it.
    assert "bad-host" in seen_worlds[0]
    assert seen_worlds[-1] == ["good-host"]


def test_worker_notification_manager(tmp_path):
    """KV poll → State.on_hosts_updated, the channel VERDICT Missing #1
    asked for."""
    import time

    from horovod_tpu.runner.http_server import RendezvousServer
    from horovod_tpu.elastic.worker import WorkerNotificationManager

    server = RendezvousServer("127.0.0.1")
    port = server.start()
    try:
        with mock.patch.dict(
            os.environ,
            {
                "HVDTPU_ELASTIC": "1",
                "HVDTPU_RENDEZVOUS_ADDR": "127.0.0.1",
                "HVDTPU_RENDEZVOUS_PORT": str(port),
                "HVDTPU_ELASTIC_POLL_SECS": "0.05",
            },
        ):
            mgr = WorkerNotificationManager()
            assert mgr.init() is True

            class FakeState:
                def __init__(self):
                    self.events = []

                def on_hosts_updated(self, ts, res):
                    self.events.append(ts)

            st = FakeState()
            mgr.register_listener(st)
            server.put("elastic", "ts", b"123.5")
            deadline = time.time() + 5
            while not st.events and time.time() < deadline:
                time.sleep(0.02)
            assert st.events == [123.5]
            # Same timestamp is not re-delivered.
            time.sleep(0.2)
            assert st.events == [123.5]
            mgr.stop()
    finally:
        server.stop()


@pytest.mark.slow
def test_cli_two_local_hosts_native_world(tmp_path, monkeypatch):
    """hvdtpu-run's per-process env must reach the native runtime: a
    2-host static launch forms a rank 0/1 world with no user wiring."""
    from horovod_tpu.runner.launch import run_commandline

    # The worker script lives under tmp_path; make the repo importable.
    monkeypatch.setenv("PYTHONPATH", REPO)

    out = tmp_path / "world.txt"
    script = tmp_path / "w.py"
    script.write_text(
        "import os\n"
        "import numpy as np\n"
        "import horovod_tpu.native as native\n"
        "native.init()\n"
        "s = native.allreduce(np.ones(4, np.float32), name='x')\n"
        f"open(r'{out}', 'a').write("
        "f'{native.rank()}/{native.size()}/{int(s[0])}\\n')\n"
        "native.shutdown()\n"
    )
    rc = run_commandline(
        ["-H", "localhost:1,127.0.0.1:1", "--", sys.executable, str(script)]
    )
    assert rc == 0
    lines = sorted(out.read_text().splitlines())
    assert lines == ["0/2/2", "1/2/2"], lines


@pytest.mark.slow
def test_programmatic_multihost_run(monkeypatch):
    """Parity: horovod.run — a pickled closure executes on every host's
    worker and results come back rank-ordered."""
    from horovod_tpu.runner.api import run

    monkeypatch.setenv("PYTHONPATH", REPO)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    offset = 1000

    def work():
        import numpy as np

        from horovod_tpu import native

        total = native.allreduce(
            np.asarray([native.rank() + 1], np.float64), name="w"
        )
        return {"rank": native.rank(), "sum": float(total[0]),
                "offset": offset}

    results = run(work, hosts="localhost:1,127.0.0.1:1")
    assert [r["rank"] for r in results] == [0, 1]
    # The collective really ran across both workers: 1 + 2 = 3.
    assert all(r["sum"] == 3.0 for r in results)
    # Closure capture survived pickling (the cloudpickle requirement).
    assert all(r["offset"] == 1000 for r in results)


def test_check_build_flag(capsys, monkeypatch):
    # Keep the fast tier fast and environment-independent: no implicit
    # C++ build, no assumptions about which frameworks this image has.
    import horovod_tpu.native as native

    monkeypatch.setattr(native, "build", lambda force=False: "")
    assert run_commandline(["--check-build"]) == 0
    out = capsys.readouterr().out
    assert "Available Frameworks:" in out
    assert "Available Controllers:" in out
    assert "[X] JAX" in out  # jax is a hard dependency of the package
    assert "native TCP" in out


_LAUNCHER_PARENT_PROBES = {
    # What hvdtpu-run does with no -H/--hostfile: count the local chips.
    "discover_tpu_hosts": (
        "import os\n"
        "os.environ['TPU_CHIPS_PER_HOST_BOUNDS'] = '2,2,1'\n"
        "from horovod_tpu.runner.hosts import discover_tpu_hosts\n"
        "hosts = discover_tpu_hosts()\n"
        "assert [h.hostname for h in hosts] == ['localhost'], hosts\n"
        "assert hosts[0].slots >= 1\n"
    ),
    "check_build": (
        "import horovod_tpu.native as native\n"
        "native.build = lambda force=False: ''\n"
        "from horovod_tpu.runner.launch import run_commandline\n"
        "assert run_commandline(['--check-build']) == 0\n"
    ),
}


@pytest.mark.parametrize("probe", sorted(_LAUNCHER_PARENT_PROBES))
def test_launcher_parent_stays_off_jax(probe):
    """A chip belongs to one process: the launcher parent must not
    initialise a JAX backend (importing jax is harmless), or it holds the
    chip the worker it spawns needs."""
    script = _LAUNCHER_PARENT_PROBES[probe] + (
        "import jax._src.xla_bridge as xb\n"
        "assert not xb.backends_are_initialized(), 'parent touched JAX'\n"
        "print('OFF_JAX')\n"
    )
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    out = subprocess.run(
        [sys.executable, "-c", script], env={**env, "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OFF_JAX" in out.stdout


def test_discover_tpu_hosts_without_chips(monkeypatch):
    """No TPU env and no device nodes: -np settles it, else a clear error
    — never a guess from a backend the parent would have to start."""
    from horovod_tpu.runner import hosts as hosts_mod

    for var in ("TPU_WORKER_HOSTNAMES", "TPU_CHIPS_PER_HOST_BOUNDS"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(hosts_mod.glob, "glob", lambda pattern: [])
    got = hosts_mod.discover_tpu_hosts(default_slots=2)
    assert [(h.hostname, h.slots) for h in got] == [("localhost", 2)]
    with pytest.raises(ValueError, match="-np"):
        hosts_mod.discover_tpu_hosts()
    # The CLI turns that into a usage error, not a traceback.
    assert run_commandline(["--", sys.executable, "-c", "pass"]) == 2


def test_discover_tpu_hosts_counts_device_nodes(monkeypatch):
    from horovod_tpu.runner import hosts as hosts_mod

    for var in ("TPU_WORKER_HOSTNAMES", "TPU_CHIPS_PER_HOST_BOUNDS"):
        monkeypatch.delenv(var, raising=False)
    nodes = {"/dev/accel[0-9]*": ["/dev/accel0", "/dev/accel1"]}
    monkeypatch.setattr(
        hosts_mod.glob, "glob", lambda pattern: nodes.get(pattern, [])
    )
    assert hosts_mod.discover_tpu_hosts()[0].slots == 2
    # The one-chip machine of a 2x2 host: the env describes the whole host,
    # the device node is what this machine was handed.
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    nodes.clear()
    nodes["/dev/vfio/[0-9]*"] = ["/dev/vfio/3"]
    got = hosts_mod.discover_tpu_hosts()
    assert [(h.hostname, h.slots) for h in got] == [("localhost", 1)]
    # Several hosts: theirs cannot be seen from here, the bounds decide.
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "w0,w1")
    got = hosts_mod.discover_tpu_hosts()
    assert [(h.hostname, h.slots) for h in got] == [("w0", 4), ("w1", 4)]


def test_rendezvous_hmac_auth():
    """Per-job HMAC (reference secret.py): signed requests pass, unsigned
    or wrong-key requests are rejected."""
    from horovod_tpu.runner.secret import make_secret_key

    key = make_secret_key()
    server = RendezvousServer("127.0.0.1", secret=key)
    port = server.start()
    try:
        good = RendezvousClient("127.0.0.1", port, timeout=5, secret=key)
        good.put("s", "k", b"v")
        assert good.get("s", "k") == b"v"
        assert good.keys("s") == ["k"]

        import urllib.error

        anon = RendezvousClient("127.0.0.1", port, timeout=5, secret="")
        with pytest.raises(urllib.error.HTTPError) as ei:
            anon.get("s", "k")
        assert ei.value.code == 403
        wrong = RendezvousClient(
            "127.0.0.1", port, timeout=5, secret=make_secret_key()
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            wrong.put("s", "k2", b"x")
        assert ei.value.code == 403
        # Value unchanged by the rejected writes.
        assert good.get("s", "k") == b"v"
    finally:
        server.stop()


def test_rendezvous_hmac_replay_rejected():
    """A byte-for-byte replay of a captured signed PUT is rejected (the
    digest covers a timestamp and the server remembers digests inside
    the window), and a stale-timestamp signature is rejected outright —
    ADVICE r2: replaying a stale round_N publication must not work."""
    import time
    import urllib.error
    import urllib.request

    from horovod_tpu.runner.secret import (
        DIGEST_HEADER,
        TS_HEADER,
        compute_digest,
        make_secret_key,
        signed_message,
    )

    key = make_secret_key()
    server = RendezvousServer("127.0.0.1", secret=key)
    port = server.start()
    try:
        path, body = "/rounds/round_7", b"host-a,host-b"

        def send(ts: str):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}", data=body, method="PUT",
                headers={
                    DIGEST_HEADER: compute_digest(
                        key, signed_message("PUT", path, ts, body)
                    ),
                    TS_HEADER: ts,
                },
            )
            return urllib.request.urlopen(req, timeout=5).read()

        now = repr(time.time())
        send(now)  # original goes through
        with pytest.raises(urllib.error.HTTPError) as ei:
            send(now)  # observer replays the exact capture
        assert ei.value.code == 403
        with pytest.raises(urllib.error.HTTPError) as ei:
            send(repr(time.time() - 3600.0))  # outside the replay window
        assert ei.value.code == 403
        # Fresh legitimate writes still work (e.g. the next round).
        good = RendezvousClient("127.0.0.1", port, timeout=5, secret=key)
        good.put("rounds", "round_8", b"host-a")
        assert good.get("rounds", "round_8") == b"host-a"
    finally:
        server.stop()


def test_output_filename_redirects_worker_logs(tmp_path):
    """Parity: --output-filename writes <dir>/rank.<N>/stdout|stderr."""
    rc = run_commandline(
        ["-H", "localhost:1", "--output-filename", str(tmp_path), "--",
         sys.executable, "-c",
         "import sys; print('to-out'); print('to-err', file=sys.stderr)"]
    )
    assert rc == 0
    assert (tmp_path / "rank.0" / "stdout").read_text().strip() == "to-out"
    assert (tmp_path / "rank.0" / "stderr").read_text().strip() == "to-err"


def test_start_timeout_flag_maps_to_env():
    from horovod_tpu.runner.launch import _args_to_env, build_parser

    args = build_parser().parse_args(
        ["--start-timeout", "90", "--log-level", "debug", "x"]
    )
    env = _args_to_env(args)
    assert env["HVT_INIT_TIMEOUT_SECONDS"] == "90"
    assert env["HVT_LOG_LEVEL"] == "debug"


# ---- NIC auto-discovery (VERDICT r3 #4; reference driver_service probe) ----


def test_nics_choose_common_intersection():
    from horovod_tpu.runner import nics

    # Reference-style fake interface tables: intersect by NAME.
    host_a = {"eth0": "10.0.0.1", "eth1": "192.168.1.1", "docker0": "172.17.0.1"}
    host_b = {"eth0": "10.0.0.2", "eth1": "192.168.9.2"}
    host_c = {"eth0": "10.0.0.3", "wlan0": "192.168.2.3"}
    assert nics.choose_common([host_a, host_b, host_c]) == "eth0"
    # Preference order: ethernet-ish names beat exotic ones.
    assert nics.choose_common(
        [{"zz0": "1.1.1.1", "ens3": "10.0.0.1"},
         {"zz0": "1.1.1.2", "ens3": "10.0.0.2"}]
    ) == "ens3"
    # No common NIC -> empty fallback (workers keep default derivation).
    assert nics.choose_common([{"eth0": "10.0.0.1"}, {"ib0": "10.1.0.2"}]) == ""
    assert nics.choose_common([]) == ""


def test_nics_list_interfaces_excludes_loopback():
    from horovod_tpu.runner import nics

    table = nics.list_interfaces()
    assert "lo" not in table
    for addr in table.values():
        assert not addr.startswith("127.")


def test_nics_driver_worker_kv_roundtrip(monkeypatch):
    """Full probe over a real rendezvous KV: two fake 'hosts' report,
    the driver intersects+publishes, workers adopt HVDTPU_IFACE."""
    from horovod_tpu.runner import nics
    from horovod_tpu.runner.http_server import RendezvousClient, RendezvousServer

    server = RendezvousServer(secret="s3")
    port = server.start()
    try:
        tables = {
            "0": {"eth0": "10.0.0.1", "eth1": "192.168.0.1"},
            "1": {"eth0": "10.0.0.2", "docker0": "172.17.0.1"},
        }
        adopted = {}
        envs = {
            pid: {nics.ENV_AUTOPROBE: "1", "HVDTPU_PROCESS_ID": pid}
            for pid in tables
        }

        import threading

        # ONE thread-aware fake for the whole test: per-thread
        # save/restore of the module global is a race — whichever
        # worker restores last can leave the other's fake installed
        # for the rest of the session (seen as a later test picking
        # up a phantom eth0).
        table_for_thread = {}
        monkeypatch.setattr(
            nics, "list_interfaces",
            lambda: table_for_thread[threading.get_ident()],
        )

        def worker(pid):
            # Per-worker env dict: several simulated workers share this
            # process, so the global os.environ must not be raced.
            table_for_thread[threading.get_ident()] = tables[pid]
            client = RendezvousClient("127.0.0.1", port, secret="s3")
            adopted[pid] = nics.worker_report_and_adopt(
                client, deadline_secs=20, env=envs[pid]
            )

        t0 = threading.Thread(target=worker, args=("0",))
        t0.start()
        import time as _t

        _t.sleep(0.3)  # let worker 0 snapshot its table first
        t1 = threading.Thread(target=worker, args=("1",))
        t1.start()
        chosen = nics.driver_autoprobe(server, n_procs=2, deadline_secs=20)
        t0.join(timeout=30)
        t1.join(timeout=30)
        assert chosen == "eth0"
        assert adopted == {"0": "eth0", "1": "eth0"}
        assert envs["0"][nics.ENV_IFACE] == "eth0"
        assert envs["1"][nics.ENV_IFACE] == "eth0"
    finally:
        server.stop()


def test_nics_partial_reports_publish_empty_fallback():
    """Only 1 of 2 workers reports before the deadline: the driver must
    publish the EMPTY fallback, not a choice the silent host never
    confirmed (a partial choice can split the world between fabric-IP
    and hostname derivation — the hang the probe exists to prevent)."""
    from horovod_tpu.runner import nics
    from horovod_tpu.runner.http_server import (
        RendezvousClient,
        RendezvousServer,
    )

    server = RendezvousServer(secret="s4")
    port = server.start()
    try:
        client = RendezvousClient("127.0.0.1", port, secret="s4")
        client.put(
            nics.SCOPE, f"{nics.REPORT_PREFIX}0",
            json.dumps({"eth0": "10.0.0.1"}).encode(),
        )
        chosen = nics.driver_autoprobe(server, n_procs=2, deadline_secs=0.5)
        assert chosen == ""
        assert server.scope_items(nics.SCOPE)[nics.CHOSEN_KEY] == b""
    finally:
        server.stop()


def test_nics_manual_override_and_disabled(monkeypatch):
    from horovod_tpu.runner import nics

    # Probe disabled: no report, no wait, returns None immediately.
    monkeypatch.delenv(nics.ENV_AUTOPROBE, raising=False)
    assert nics.worker_report_and_adopt(client=None) is None
    # Manual HVDTPU_IFACE wins without touching the KV.
    monkeypatch.setenv(nics.ENV_AUTOPROBE, "1")
    monkeypatch.setenv(nics.ENV_IFACE, "ethX")
    assert nics.worker_report_and_adopt(client=None) == "ethX"


def test_launch_job_autoprobe_gating(monkeypatch):
    """Local-only worlds must NOT engage the probe; multi-host worlds
    must inject HVDTPU_NIC_AUTOPROBE (manual iface disables it)."""
    import horovod_tpu.runner.api as api

    captured = []

    class FakeJob:
        def __init__(self, hostname, cmd, env, output_dir=None, rank=0):
            self.hostname = hostname
            captured.append(env)

        def poll(self):
            return 0

        def terminate(self):
            pass

    monkeypatch.setattr(api, "_Job", FakeJob)
    hosts = api.parse_hosts("localhost:1,127.0.0.1:1")
    assert api.launch_job(["true"], hosts, poll_interval=0.01) == 0
    assert all("HVDTPU_NIC_AUTOPROBE" not in env for env in captured)

    captured.clear()
    remote = api.parse_hosts("nodeA:1,nodeB:1")
    assert api.launch_job(["true"], remote, poll_interval=0.01) == 0
    assert all(env.get("HVDTPU_NIC_AUTOPROBE") == "1" for env in captured)

    captured.clear()
    from horovod_tpu.runner import nics

    real = next(iter(nics.list_interfaces()), None)
    if real is None:
        pytest.skip("host has no non-loopback interface")
    monkeypatch.setenv("HVDTPU_IFACE", real)
    assert api.launch_job(["true"], remote, poll_interval=0.01) == 0
    assert all("HVDTPU_NIC_AUTOPROBE" not in env for env in captured)
