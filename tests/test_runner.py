"""Launcher tests: arg/host parsing, env construction, services, safe
exec, rendezvous auth, and a real static end-to-end run on localhost
(reference: test/single/test_run.py + test/integration/test_static_run.py)."""

import os

from tests.utils.spawn import run_world, scaled_timeout
import sys
import time

import numpy as np
import pytest

from horovod_tpu.runner import util
from horovod_tpu.runner.launch import (build_common_env, gloo_run,
                                       parse_args, worker_env,
                                       _slot_assignments)
from horovod_tpu.runner.http_client import RendezvousClient
from horovod_tpu.runner.http_server import RendezvousServer
from horovod_tpu.runner.services import DriverService, TaskService
from horovod_tpu.runner import safe_shell_exec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_hosts():
    hosts = util.parse_hosts("a:4,b:2,c")
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("a", 4), ("b", 2), ("c", 1)]
    assert util.total_slots(hosts) == 7
    with pytest.raises(ValueError):
        util.parse_hosts("")


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hostfile"
    f.write_text("# comment\nnode1 slots=4\nnode2:2\n")
    hosts = util.parse_hostfile(str(f))
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("node1", 4), ("node2", 2)]


def test_slot_assignments():
    hosts = util.parse_hosts("a:2,b:2")
    slots, cross = _slot_assignments(hosts, 3)
    assert cross == 2
    assert [(s[0], s[1], s[2]) for s in slots] == [
        ("a", 0, 0), ("a", 1, 1), ("b", 2, 0)]
    with pytest.raises(ValueError):
        _slot_assignments(hosts, 9)


def test_parse_args_and_env():
    args = parse_args(["-np", "2", "--fusion-threshold-mb", "8",
                       "--cycle-time-ms", "2", "--autotune",
                       "--timeline-filename", "/tmp/tl",
                       "python", "train.py"])
    assert args.np == 2 and args.command == ["python", "train.py"]
    env = build_common_env(args, {})
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(8 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "2.0"
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_TIMELINE"] == "/tmp/tl"
    wenv = worker_env(env, 1, 2, 1, 2, 0, 1, "127.0.0.1:9", "s", 29600)
    assert wenv["HOROVOD_RANK"] == "1"
    assert wenv["HOROVOD_CONTROLLER"] == "tcp"


def test_worker_env_gives_each_local_slot_its_own_chip():
    from horovod_tpu.runner import launch

    def tpu_vars(local_rank, local_size):
        env = launch.worker_env({}, local_rank, local_size, local_rank,
                                local_size, 0, 1, "127.0.0.1:9", "s", 29600)
        return {k: v for k, v in env.items() if "TPU" in k}

    # A lone slot is left alone: it takes every chip of its host.
    assert tpu_vars(0, 1) == {}
    four = [tpu_vars(r, 4) for r in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in four] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in four}) == 4
    # ... and together they are one 2x2 topology of one-chip processes.
    for rank, e in enumerate(four):
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert e["CLOUD_TPU_TASK_ID"] == str(rank)
        assert e["TPU_PROCESS_ADDRESSES"] == four[0]["TPU_PROCESS_ADDRESSES"]
        assert ("localhost:" + e["TPU_PROCESS_PORT"]
                == e["TPU_PROCESS_ADDRESSES"].split(",")[rank])
        assert set(e) == launch._CHIP_ENV
    # ... which travels to a remote host with the HOROVOD_* world.
    remote = launch._ssh_wrap("far", 22, dict(four[1], HOME="/x"), ["t"])[-1]
    assert "TPU_VISIBLE_CHIPS=1" in remote and "HOME=" not in remote
    # A slot count with no known grid still never shares a chip.
    assert [tpu_vars(r, 3)["TPU_VISIBLE_CHIPS"] for r in range(3)] \
        == ["0", "1", "2"]
    assert tpu_vars(1, 3)["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_package_import_is_framework_free(tmp_path):
    # The lazy top-level namespace (PEP 562, reference: slim
    # horovod/__init__.py) must not pull jax: launcher-only hosts run
    # `python -m horovod_tpu.runner` framework-free.  Simulate a
    # jax-less host with a raising stub on PYTHONPATH.
    (tmp_path / "jax.py").write_text(
        "raise ImportError('no jax on this host (simulated)')\n")
    code = ("import horovod_tpu, horovod_tpu.runner; "
            "assert horovod_tpu.__version__; "
            "from horovod_tpu.runner.launch import check_build; "
            "import io; buf = io.StringIO(); check_build(out=buf); "
            "assert '[ ] JAX' in buf.getvalue(); "
            "print('LAZY_OK')")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "%s%s%s" % (tmp_path, os.pathsep, REPO)
    proc = run_world([sys.executable, "-c", code], timeout=120, env=env,
                     cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "LAZY_OK" in proc.stdout


def test_check_build_matrix():
    # Reference `horovodrun --check-build`: feature matrix prints and
    # exits 0 without a worker command.
    import io
    from horovod_tpu.runner.launch import check_build, parse_args
    args = parse_args(["--check-build"])
    assert args.check_build and args.command == []
    buf = io.StringIO()
    assert check_build(out=buf) == 0
    text = buf.getvalue()
    assert "Available Frameworks" in text
    assert "[X] JAX" in text
    assert "Available Controllers" in text
    assert "Available Tensor Operations" in text
    assert "[ ] NCCL" in text  # absent by design, honestly reported


def test_cli_backend_flags():
    from horovod_tpu.runner.launch import parse_args
    args = parse_args(["--gloo", "-np", "2", "python", "x.py"])
    assert args.gloo and args.np == 2
    with pytest.raises(SystemExit):
        parse_args(["--mpi", "-np", "2", "python", "x.py"])


def test_parse_args_requires_command():
    with pytest.raises(SystemExit):
        parse_args(["-np", "2"])


def test_safe_shell_exec_streams_and_kills():
    lines = []
    rc = safe_shell_exec.execute(
        [sys.executable, "-c", "print('hello'); print('world')"],
        stdout_sink=lines.append)
    assert rc == 0
    assert "".join(lines) == "hello\nworld\n"
    # Termination of a hanging tree.
    mp = safe_shell_exec.ManagedProcess(
        [sys.executable, "-c", "import time; time.sleep(600)"])
    t0 = time.monotonic()
    mp.terminate()
    assert mp.proc.poll() is not None
    assert time.monotonic() - t0 < safe_shell_exec.\
        GRACEFUL_TERMINATION_TIME_S + 2


def test_rpc_transient_classification():
    # Transient: the peer (or the path to it) is momentarily gone.
    import urllib.error
    from horovod_tpu.runner.http_client import is_transient
    assert is_transient(ConnectionRefusedError("refused"))
    assert is_transient(ConnectionResetError("reset"))
    assert is_transient(TimeoutError("slow"))
    assert is_transient(
        urllib.error.URLError(ConnectionRefusedError("refused")))
    assert is_transient(
        urllib.error.HTTPError("u", 500, "handler died", {}, None))
    assert is_transient(
        urllib.error.HTTPError("u", 503, "overloaded", {}, None))
    # Local resource pressure (fd / ephemeral-port exhaustion from
    # per-poll connections) passes as the kernel recycles — retry.
    import errno
    assert is_transient(OSError(errno.EMFILE, "too many open files"))
    assert is_transient(OSError(errno.EADDRNOTAVAIL, "no free ports"))
    # Fatal: the server answered, and the answer is "no".
    assert not is_transient(
        urllib.error.HTTPError("u", 403, "bad secret", {}, None))
    assert not is_transient(
        urllib.error.HTTPError("u", 400, "bad request", {}, None))
    assert not is_transient(PermissionError("bad MAC"))
    assert not is_transient(ValueError("not an rpc failure at all"))


def test_request_with_retry_absorbs_transient_failures():
    from horovod_tpu.runner.http_client import request_with_retry
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionResetError("flake")
        return "ok"

    assert request_with_retry(flaky, backoff=0.01) == "ok"
    assert len(calls) == 3


def test_request_with_retry_never_retries_fatal():
    from horovod_tpu.runner.http_client import request_with_retry
    calls = []

    def fatal():
        calls.append(1)
        raise PermissionError("auth rejection")

    with pytest.raises(PermissionError):
        request_with_retry(fatal, backoff=0.01)
    assert len(calls) == 1


def test_request_with_retry_exhaustion_raises_last_error():
    from horovod_tpu.runner.http_client import request_with_retry
    calls = []

    def always_down():
        calls.append(1)
        raise ConnectionRefusedError("down for good")

    with pytest.raises(ConnectionRefusedError):
        request_with_retry(always_down, max_retries=2, backoff=0.01)
    assert len(calls) == 3  # first attempt + 2 retries


def test_request_with_retry_respects_deadline():
    from horovod_tpu.runner.http_client import request_with_retry

    def always_down():
        raise ConnectionRefusedError("down")

    t0 = time.monotonic()
    with pytest.raises(ConnectionRefusedError):
        request_with_retry(always_down, max_retries=1000,
                           backoff=0.05, deadline=0.3)
    assert time.monotonic() - t0 < 5.0


class _FlakyStore(dict):
    """KV store whose first N writes raise (server-side handler crash
    → the server answers 500, which the client must retry)."""

    def __init__(self, failures: int):
        super().__init__()
        self.failures = failures

    def __setitem__(self, key, value):
        if self.failures > 0:
            self.failures -= 1
            raise RuntimeError("injected store failure")
        dict.__setitem__(self, key, value)


def test_rendezvous_5xx_is_retried(monkeypatch):
    # A crashing PUT handler answers 500 (not a torn connection); the
    # client's retry layer absorbs it and the write lands.
    monkeypatch.setenv("HOROVOD_RPC_RETRY_BACKOFF", "0.01")
    server = RendezvousServer(secret="s")
    port = server.start()
    try:
        server._httpd.store = _FlakyStore(failures=2)
        client = RendezvousClient("127.0.0.1:%d" % port, secret="s")
        client.put("addr/0", "1.2.3.4:5")
        assert client.get("addr/0") == "1.2.3.4:5"
    finally:
        server.stop()


def test_rendezvous_auth_403_fails_immediately(monkeypatch):
    # An HMAC rejection is fatal: no backoff sleep may happen on the
    # way to the raise (retrying an auth failure hammers the server
    # with requests it already refused).
    import urllib.error

    def no_sleep(_secs):
        raise AssertionError("403 must not be retried")

    server = RendezvousServer(secret="right")
    port = server.start()
    try:
        monkeypatch.setattr(time, "sleep", no_sleep)
        bad = RendezvousClient("127.0.0.1:%d" % port, secret="wrong")
        with pytest.raises(urllib.error.HTTPError) as err:
            bad.put("addr/0", "x")
        assert err.value.code == 403
    finally:
        monkeypatch.undo()
        server.stop()


def test_rpc_drop_and_recover_end_to_end():
    """Self-healing RPC plane, certified by injection: every process's
    first two control-plane RPC attempts fail with a synthetic
    connection reset (HVD_TPU_FAULT runner.rpc.request, @times=2), and
    the run must still complete — the retry/backoff layer absorbs the
    transient window."""
    script = (
        "import horovod_tpu as hvd, numpy as np\n"
        "hvd.init()\n"
        "out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,"
        " name='t')\n"
        "np.testing.assert_allclose(np.asarray(out), 2.0)\n"
        "print('RANK_OK', hvd.rank())\n"
        "hvd.shutdown()\n")
    env = _worker_env()
    env["HVD_TPU_FAULT"] = "runner.rpc.request:drop@times=2"
    env["HOROVOD_RPC_RETRY_BACKOFF"] = "0.05"
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, "-c", script],
        timeout=90,
        env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(2):
        assert "RANK_OK %d" % r in proc.stdout


def test_rpc_retry_exhaustion_fails_loudly():
    """The escalation boundary: with the drop armed permanently, the
    bounded retry budget exhausts and the run FAILS (non-zero rc,
    bounded wall time) — transient-fault absorption never downgrades a
    persistent fault into a hang."""
    script = (
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "print('UNREACHED')\n")
    env = _worker_env()
    env["HVD_TPU_FAULT"] = "runner.rpc.request:drop"
    env["HOROVOD_RPC_MAX_RETRIES"] = "2"
    env["HOROVOD_RPC_RETRY_BACKOFF"] = "0.05"
    env["HOROVOD_RPC_DEADLINE"] = "5"
    t0 = time.monotonic()
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, "-c", script],
        timeout=120,
        env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "UNREACHED" not in proc.stdout
    assert "injected transient RPC failure" in proc.stdout + proc.stderr
    assert time.monotonic() - t0 < scaled_timeout(90)


def test_rendezvous_kv_and_auth():
    server = RendezvousServer(secret="topsecret")
    port = server.start()
    try:
        good = RendezvousClient("127.0.0.1:%d" % port, secret="topsecret")
        good.put("addr/0", "1.2.3.4:5")
        assert good.get("addr/0") == "1.2.3.4:5"
        assert good.get("missing") is None
        bad = RendezvousClient("127.0.0.1:%d" % port, secret="wrong")
        with pytest.raises(Exception):
            bad.put("addr/1", "x")
        assert good.get("addr/1") is None  # unauthorized write rejected
        good.delete("addr/0")
        assert good.get("addr/0") is None
    finally:
        server.stop()


def test_driver_task_services():
    task = TaskService(index=3, secret="s3cr3t")
    port = task.start()
    try:
        driver = DriverService(secret="s3cr3t")
        info = driver.probe(("127.0.0.1", port))
        assert info["index"] == 3
        assert "127.0.0.1" in info["addresses"]
        got = []
        task.on_notify(got.append)
        driver.notify(("127.0.0.1", port), {"hosts": ["a:1"]})
        assert got == [{"hosts": ["a:1"]}]
        # Wrong secret is rejected (connection dropped / no valid reply).
        bad = DriverService(secret="wrong")
        with pytest.raises(Exception):
            bad.probe(("127.0.0.1", port), timeout=2.0)
    finally:
        task.stop()


def test_task_service_proc_poll_distinguishes_no_proc():
    # An agent with NO process (restarted, lost state) must not read as
    # "running" forever: proc_poll carries has_proc so the elastic
    # driver's _AgentProc treats it as a failed spawn and retries.
    from horovod_tpu.runner.services import send_message
    from horovod_tpu.spark.elastic import _AgentProc
    task = TaskService(index=0, secret="k")
    port = task.start()
    try:
        resp = send_message(("127.0.0.1", port), "k",
                            {"kind": "proc_poll"}, timeout=5.0)
        assert resp == {"rc": None, "has_proc": False}
        proxy = _AgentProc(("127.0.0.1", port), "k")
        assert proxy.poll() == 1  # no-proc reads as failed, not alive
        # A real (running) proc reads as alive, then its exit code.
        send_message(("127.0.0.1", port), "k",
                     {"kind": "run", "cmd": ["__PYTHON__", "-c",
                                             "import time; time.sleep(5)"],
                      "env": {}}, timeout=5.0)
        resp = send_message(("127.0.0.1", port), "k",
                            {"kind": "proc_poll"}, timeout=5.0)
        assert resp["has_proc"] is True and resp["rc"] is None
        send_message(("127.0.0.1", port), "k",
                     {"kind": "proc_stop"}, timeout=5.0)
    finally:
        task.stop()


def _worker_env():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_static_run_end_to_end():
    """Real launcher e2e: 3 local workers init tcp mode via rendezvous,
    allreduce, and verify identity env plumbed by the launcher."""
    script = (
        "import horovod_tpu as hvd, numpy as np\n"
        "hvd.init()\n"
        "assert hvd.size() == 3\n"
        "out = hvd.allreduce(np.ones(4, np.float32) * hvd.rank(),"
        " op=hvd.Sum, name='t')\n"
        "np.testing.assert_allclose(np.asarray(out), 3.0)\n"
        "assert hvd.local_size() == 3\n"
        "print('RANK_OK', hvd.rank())\n"
        "hvd.shutdown()\n")
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "3",
         sys.executable, "-c", script],
        timeout=90, env=_worker_env(),
        cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(3):
        assert "RANK_OK %d" % r in proc.stdout


def test_static_run_failure_tears_down_world():
    """One worker exits non-zero -> launcher kills the rest and reports
    failure (reference exit-propagation behavior)."""
    script = (
        "import os, time\n"
        "if os.environ['HOROVOD_RANK'] == '1':\n"
        "    raise SystemExit(3)\n"
        "time.sleep(600)\n")
    t0 = time.monotonic()
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, "-c", script],
        timeout=60, env=_worker_env(),
        cwd=REPO)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60


def test_programmatic_run():
    from tests.utils.run_fn import rank_times_two
    from horovod_tpu.runner import run
    results = run(rank_times_two, np=2)
    assert results == [0, 2]


def test_programmatic_run_backend_kwargs():
    # Reference-signature compatibility: use_gloo accepted (TCP IS the
    # gloo-equivalent plane), use_mpi rejected loudly (absent by
    # design).
    from horovod_tpu.runner import run
    from tests.utils.run_fn import rank_times_two
    assert run(rank_times_two, np=1, use_gloo=True) == [0]
    with pytest.raises(ValueError, match="MPI"):
        run(rank_times_two, np=1, use_mpi=True)


def test_programmatic_run_elastic():
    # Reference horovod.run elastic parameters: min_np routes through
    # the elastic driver; results are the final world's per-rank
    # values over a real driver-rendezvous'd world.
    from tests.utils.run_fn import elastic_rank_value
    from horovod_tpu.runner import run
    results = run(elastic_rank_value, np=2, min_np=2,
                  elastic_timeout=60)
    assert results == [2, 12]


def test_lsf_host_parsing(monkeypatch):
    from horovod_tpu.runner import util
    monkeypatch.setenv("LSB_MCPU_HOSTS", "nodeA 4 nodeB 2")
    assert util.lsf_available()
    hosts = util.parse_lsf_hosts()
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("nodeA", 4), ("nodeB", 2)]
    monkeypatch.delenv("LSB_MCPU_HOSTS")
    monkeypatch.setenv("LSB_HOSTS", "n1 n1 n1 n2")
    hosts = util.parse_lsf_hosts()
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("n1", 3), ("n2", 1)]


def test_slurm_host_parsing(monkeypatch):
    from horovod_tpu.runner import util
    monkeypatch.setenv("SLURM_JOB_NODELIST", "node[01-03,07],gpu5")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "4(x3),2")
    assert util.slurm_available()
    hosts = util.parse_slurm_hosts()
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("node01", 4), ("node02", 4), ("node03", 4), ("node07", 2),
        ("gpu5", 2)]


def test_scheduler_hosts_fallback(monkeypatch):
    from horovod_tpu.runner import util
    for var in ("LSB_MCPU_HOSTS", "LSB_HOSTS", "SLURM_JOB_NODELIST",
                "SLURM_NODELIST"):
        monkeypatch.delenv(var, raising=False)
    assert util.scheduler_hosts() == []


def test_lsf_interleaved_hosts(monkeypatch):
    from horovod_tpu.runner import util
    monkeypatch.delenv("LSB_MCPU_HOSTS", raising=False)
    monkeypatch.setenv("LSB_HOSTS", "n1 n2 n1 n2")
    hosts = util.parse_lsf_hosts()
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("n1", 2), ("n2", 2)]


def test_scheduler_hosts_warns_on_malformed(monkeypatch, capsys):
    from horovod_tpu.runner import util
    monkeypatch.setenv("LSB_MCPU_HOSTS", "host1 4 host2")  # odd tokens
    for var in ("SLURM_JOB_NODELIST", "SLURM_NODELIST"):
        monkeypatch.delenv(var, raising=False)
    assert util.scheduler_hosts() == []
    assert "LSF detected but unusable" in capsys.readouterr().err


def test_undersized_scheduler_allocation_hard_fails(monkeypatch):
    # A Slurm/LSF allocation smaller than -np must abort (reference
    # launcher behavior), not silently oversubscribe the batch node.
    import pytest
    from horovod_tpu.runner import launch
    monkeypatch.setenv("SLURM_JOB_NODELIST", "node01")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "2")
    with pytest.raises(SystemExit, match="2 slots < -np 4"):
        launch.run_commandline(["-np", "4", "true"])


def test_programmatic_run_env_overlay_does_not_leak():
    # run(env=...) reaches the workers but never mutates the caller env.
    import os
    from horovod_tpu.runner.run_api import run
    assert "HVD_TPU_TEST_OVERLAY" not in os.environ
    out = run(_echo_overlay, np=2, env={"HVD_TPU_TEST_OVERLAY": "yes"})
    assert out == ["yes", "yes"]
    assert "HVD_TPU_TEST_OVERLAY" not in os.environ


def _echo_overlay():
    import os
    return os.environ.get("HVD_TPU_TEST_OVERLAY")
