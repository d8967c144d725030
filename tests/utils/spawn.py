"""Shared multi-process world spawner for adapter tests.

Real subprocess worlds over localhost TCP rendezvous — the reference's
``horovodrun -np N pytest`` strategy (SURVEY.md §4) without the
launcher wrapper.  Ports are probed for bindability before committing
to a base (earlier suite tests leave lingering sockets; a collision
hangs the rendezvous rather than failing fast).
"""

import os
import signal
import socket
import subprocess
import sys
import time


def kill_proc_tree(proc):
    """SIGKILL a spawned worker's whole process group (it leads one:
    spawn_world starts each rank with ``start_new_session=True``), then
    the process itself as a fallback."""
    try:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        pass
    try:
        proc.kill()
    except OSError:
        pass


def scaled_timeout(seconds: float) -> float:
    """Spawn/rendezvous timeouts scaled by HVD_TPU_TEST_TIMEOUT_SCALE.

    Timeouts here are calibrated for an idle 1-core box; any
    contention (a parallel judge workload, concurrent shards) tips
    spawn-heavy tests into timeout flakes (r4: two such).  One knob
    scales every harness-level timeout rather than re-tuning each
    call site: ``HVD_TPU_TEST_TIMEOUT_SCALE=2 pytest ...``.
    """
    try:
        scale = float(os.environ.get("HVD_TPU_TEST_TIMEOUT_SCALE", "1"))
    except ValueError:
        scale = 1.0
    return seconds * max(scale, 0.1)


def _text(data) -> str:
    if data is None:
        return ""
    return data if isinstance(data, str) else data.decode(errors="replace")


def world_timed_out(what, limit, outputs):
    """The failure of a world that outlived its limit: a
    ``TimeoutExpired`` alone throws away what the children had written,
    which is the only record of where the world stood."""
    return AssertionError(
        "%s timed out after %.0f s; what its processes had written:\n%s"
        % (what, limit, "\n".join(
            "---- %s stdout ----\n%s\n---- %s stderr ----\n%s"
            % (name, _text(out)[-20000:], name, _text(err)[-20000:])
            for name, out, err in outputs)))


def run_world(cmd, timeout, **kwargs):
    """``subprocess.run(cmd, capture_output=True, text=True)`` under
    ``scaled_timeout(timeout)`` for a command that starts processes of
    its own (a launcher and its workers).  At the limit the whole
    process group dies, not the launcher alone, and the failure shows
    what every process had written (``world_timed_out``)."""
    limit = scaled_timeout(timeout)
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True, **kwargs)
    try:
        out, err = proc.communicate(timeout=limit)
    except subprocess.TimeoutExpired:
        kill_proc_tree(proc)
        out, err = proc.communicate()
        raise world_timed_out(" ".join(map(str, cmd)), limit,
                              [("launcher", out, err)]) from None
    return subprocess.CompletedProcess(cmd, proc.returncode, out, err)


# The harness's ports lie under the kernel's own range (32768-60999 here,
# /proc/sys/net/ipv4/ip_local_port_range): there every outgoing connection
# and every bind to port 0 of any process on the box draws a port at
# random, and one drawn between the probe below and the world's own bind
# takes a rank's port from under it ("native core init failed").  The
# launcher draws its own bases from the 6,000 ports above them
# (runner/util.py: find_free_port_base).
_SLOT_PORTS = 500   # ports per (worker, shard) slot
_SLOT_COUNT = 31    # 10500 + 31*500 = 26000
_BASE_FLOOR = 10500


def _initial_port_base() -> int:
    # Disjoint ranges per pytest-xdist worker (and per run_sharded.py
    # shard): two processes probing the same base can both see a port
    # free (probe binds then closes) and collide when their spawned
    # worlds bind for real.  Slots are (worker + 8*shard) mod 31 —
    # collision-free for up to 8 workers x 3 shards concurrently on
    # one host (and any single dimension up to 31); beyond capacity
    # slots wrap, degrading to probe-time detection rather than
    # running into the kernel's range.
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")
    idx = int(worker[2:]) if worker.startswith("gw") and \
        worker[2:].isdigit() else 0
    shard = os.environ.get("HVD_TPU_TEST_PORT_SHARD", "")
    if shard.isdigit():
        idx += int(shard) * 8
    return _BASE_FLOOR + (idx % _SLOT_COUNT) * _SLOT_PORTS


_port_base = [_initial_port_base()]


def free_port_block(size, extra_offsets=()):
    """A base where [base, base+size) plus any extra offsets bind."""
    hi = max(size, *extra_offsets) if extra_offsets else size
    floor = _initial_port_base()
    for _ in range(200):
        _port_base[0] += size + 30
        # A file of many worlds walks its slot round and round and never
        # into a neighbour's, whose worlds form at the same moment (the
        # binds below confirm that the last lap's world has gone).
        if _port_base[0] + hi >= floor + _SLOT_PORTS:
            _port_base[0] = floor
        base = _port_base[0]
        socks = []
        try:
            for port in ([base + i for i in range(size)]
                         + [base + o for o in extra_offsets]):
                s = socket.socket()
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", port))
                socks.append(s)
            return base
        except (OSError, OverflowError):
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port block found")


def spawn_world(worker, size, extra_env=None, timeout=120, retry=True,
                extra_port_offsets=(), pop_env=()):
    """Run `worker` as `size` rank processes; returns [(rc, out, err)].
    ``timeout`` is the limit of one attempt at the whole world; a world
    that outlives it is tried once more, so the call waits at most twice
    that (keep it at 150 s or under) and then fails with what every rank
    had written (``world_timed_out``)."""
    unscaled, timeout = timeout, scaled_timeout(timeout)
    base = free_port_block(size, extra_port_offsets)
    procs = []
    for rank in range(size):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        for key in pop_env:
            env.pop(key, None)
        env.update({
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(size),
            "HOROVOD_PORT_BASE": str(base),
        })
        # Default pin, caller-overridable: 1 ms negotiation cycles keep
        # spawn-heavy tests fast, but an explicit cycle-time env
        # legitimately suppresses the plan-cache tuned-point warm start
        # (env wins under the config precedence rule), so a world that
        # must model a default-config rerun names the key in
        # ``pop_env`` and gets a truly unset env — not a silent pin.
        # Every key pinned by this harness must be documented in
        # tests/README.md (the env-harness-pin lint check enforces it).
        if "HOROVOD_CYCLE_TIME" not in pop_env:
            env["HOROVOD_CYCLE_TIME"] = "1"
        env.update(extra_env or {})
        # Each rank leads its own process group (start_new_session) so
        # teardown can kill the whole tree: a worker that itself forked
        # (an elastic driver's children, a wedged grandchild) must not
        # outlive the test that spawned it.
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            start_new_session=True))
    outs = []
    deadline = time.monotonic() + timeout
    for p in procs:
        try:
            out, err = p.communicate(
                timeout=max(deadline - time.monotonic(), 0.1))
        except subprocess.TimeoutExpired:
            for q in procs:
                kill_proc_tree(q)
            failure = world_timed_out(
                "a world of %d of %s" % (size, worker), timeout,
                [("rank %d (rc=%d)" % (rank, rc), out, err)
                 for rank, (rc, out, err) in enumerate(outs)]
                + [("rank %d" % rank, *q.communicate())
                   for rank, q in enumerate(procs) if rank >= len(outs)])
            if not retry:
                raise failure from None
            print("retrying once: %s" % failure, file=sys.stderr)
            return spawn_world(worker, size, extra_env, unscaled,
                               retry=False,
                               extra_port_offsets=extra_port_offsets,
                               pop_env=pop_env)
        outs.append((p.returncode, out.decode(), err.decode()))
    return outs


def assert_world_ok(outs, marker=None):
    for rank, (rc, out, err) in enumerate(outs):
        assert rc == 0, "rank %d failed (rc=%d):\n%s\n%s" % (rank, rc,
                                                             out, err)
        if marker is not None:
            assert "%s %d" % (marker, rank) in out, out
