"""Test world setup: 8 virtual CPU devices.

Mirrors the reference's test strategy (SURVEY.md §4): the cheap real-wire
test backend there is Gloo-on-localhost; ours is JAX CPU with
``--xla_force_host_platform_device_count=8`` — a real 8-"chip" world where
XLA collectives actually execute, no mocks.

Must run before any test imports initialize a JAX backend: the
environment is set for the processes the suite spawns, and jax.config for
this one, which wins as long as no backend has been created yet.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Session tag inherited by every process this suite spawns (directly or
# through the launcher/driver): the orphan reaper only ever touches
# processes carrying it, so unrelated Horovod jobs on the box — or a
# concurrent shard's workers — are never swept.
os.environ["HVD_TPU_TEST_SESSION"] = str(os.getpid())

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def flash_blocks(monkeypatch):
    """``pin(block_q, block_k, backward)``: every flash call of a test takes
    these blocks whatever its shape (the plan's chains and its cap at the
    window set aside, so that a block may be wider than a band), and with
    ``backward="pallas"`` the two backward kernels (no dq fits the one
    kernel's budget); ``"pallas_onepass"`` or None leave the backward form
    to the shape, which at a test's sizes is the one kernel."""
    from horovod_tpu.ops import pallas_kernels as pk
    plan, budget = pk._plan, pk._ONEPASS_VMEM_BUDGET

    def pin(block_q=None, block_k=None, backward=None):
        if block_q is not None:
            monkeypatch.setattr(pk, "_plan", lambda *a, **kw: (
                block_q, block_k) + plan(*a, **kw)[2:])
        monkeypatch.setattr(pk, "_ONEPASS_VMEM_BUDGET",
                            0 if backward == "pallas" else budget)
    return pin


@pytest.fixture
def hvd_world():
    """Initialized in-process world over the 8 CPU devices; torn down after."""
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


# -- orphan reaper ----------------------------------------------------------

def _horovod_orphans():
    """PIDs of orphaned Horovod worker processes spawned by THIS
    session: the session tag (``HVD_TPU_TEST_SESSION=<our pid>``,
    exported above and inherited by every spawned tree) plus a
    ``HOROVOD_*`` world/elastic marker in the environment, AND a dead
    parent (ppid reparented to init / this process).  The tag keeps
    unrelated Horovod jobs and concurrent shards out of the sweep; a
    live parent means some still-running harness owns the process."""
    if not os.path.isdir("/proc"):
        return []
    me = os.getpid()
    session_tag = ("HVD_TPU_TEST_SESSION=%d" % me).encode()
    markers = (b"HOROVOD_RANK=", b"HOROVOD_ELASTIC_DRIVER_ADDR=",
               b"HOROVOD_ELASTIC_SLOT=")
    orphans = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        pid = int(name)
        if pid == me:
            continue
        try:
            with open("/proc/%d/environ" % pid, "rb") as f:
                environ = f.read()
            # Exact entry match (split on NUL) so session pid 123
            # never claims session 1234's workers.
            if session_tag not in environ.split(b"\0"):
                continue
            if not any(m in environ for m in markers):
                continue
            with open("/proc/%d/stat" % pid) as f:
                stat = f.read()
            ppid = int(stat.rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue  # exited mid-scan / not ours to read
        if ppid in (1, me):
            orphans.append(pid)
    return orphans


@pytest.fixture(scope="session", autouse=True)
def _reap_orphaned_workers():
    """Session teardown sweep: any elastic/multihost worker process that
    outlived its test is killed (whole process group) and FAILS the
    session loudly — a leaked worker is a failed teardown path, exactly
    the class of bug the fault-injection suite exists to catch."""
    yield
    import signal
    import time as _time
    orphans = _horovod_orphans()
    for pid in orphans:
        try:
            # Never killpg our own group: an orphan that was spawned
            # without start_new_session shares pytest's pgid, and
            # sweeping that group would SIGKILL the session itself.
            if os.getpgid(pid) != os.getpgrp():
                os.killpg(os.getpgid(pid), signal.SIGKILL)
            else:
                os.kill(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    if orphans:
        _time.sleep(0.5)
        survivors = set(_horovod_orphans()) & set(orphans)
        raise RuntimeError(
            "orphaned Horovod worker processes survived the suite "
            "(pids %s, killed now%s) — some test's teardown leaked its "
            "world" % (sorted(orphans),
                       "" if not survivors else
                       "; STILL ALIVE: %s" % sorted(survivors)))
