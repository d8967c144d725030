"""Elastic training tests.

Reference parity: ``test/integration/test_elastic_torch.py`` + the
elastic driver unit tests — discovery/registry/sampler/state units, and
real-process integration runs where a worker is killed mid-training
(failure → blacklist → resume from commit) and where the discovery
script's output is mutated mid-run (scale-up → re-rendezvous), with
multi-host faked as loopback-alias hosts on localhost.
"""

import os
import subprocess
import sys
import threading

from tests.utils.spawn import run_world, scaled_timeout
import time

import numpy as np
import pytest

from horovod_tpu.elastic.discovery import (FixedHosts, HostDiscoveryScript,
                                           HostManager, HostUpdateResult)
from horovod_tpu.elastic.registration import WorkerStateRegistry
from horovod_tpu.elastic.sampler import ElasticSampler
from horovod_tpu.elastic.state import ObjectState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- units -----------------------------------------------------------------

def test_discovery_script_parsing(tmp_path):
    script = tmp_path / "disc.sh"
    script.write_text("#!/bin/sh\necho host1:4\necho '# comment'\n"
                      "echo host2\n")
    script.chmod(0o755)
    disc = HostDiscoveryScript(str(script), default_slots=2)
    assert disc.find_available_hosts_and_slots() == {
        "host1": 4, "host2": 2}


def test_discovery_script_failure(tmp_path):
    script = tmp_path / "disc.sh"
    script.write_text("#!/bin/sh\nexit 7\n")
    script.chmod(0o755)
    with pytest.raises(RuntimeError):
        HostDiscoveryScript(str(script)).find_available_hosts_and_slots()


def test_host_manager_diffs_and_blacklist():
    registry = WorkerStateRegistry()
    hosts = {"a": 2, "b": 1}
    disc = FixedHosts(hosts)
    hm = HostManager(disc, registry.is_blacklisted)
    assert hm.update_available_hosts() == HostUpdateResult.ADDED
    assert hm.update_available_hosts() == HostUpdateResult.NO_UPDATE
    hosts["c"] = 1
    disc._hosts["c"] = 1
    assert hm.update_available_hosts() == HostUpdateResult.ADDED
    registry.record_failure("b")
    assert registry.is_blacklisted("b")
    assert hm.update_available_hosts() == HostUpdateResult.REMOVED
    assert "b" not in hm.current_hosts
    assert hm.ordered_slots(max_np=2) == [("a", 0), ("a", 1)]
    assert hm.ordered_slots() == [("a", 0), ("a", 1), ("c", 0)]


def test_worker_state_registry_threshold():
    reg = WorkerStateRegistry(failure_threshold=2)
    assert not reg.record_failure("h")
    assert not reg.is_blacklisted("h")
    assert reg.record_failure("h")
    assert reg.is_blacklisted("h")
    reg2 = WorkerStateRegistry()
    reg2.record_failure("x")
    assert reg2.blacklisted_hosts() == ["x"]


def test_worker_state_registry_cooldown_zero_is_permanent():
    # Satellite of the cooldown wiring: the default (0) must still mean
    # "blacklisted forever" (reference parity) — record_success clears
    # the failure streak but never lifts an active blacklist entry.
    reg = WorkerStateRegistry(failure_threshold=1, cooldown_secs=0.0)
    assert reg.record_failure("h")
    assert reg.is_blacklisted("h")
    time.sleep(0.05)
    assert reg.is_blacklisted("h")
    reg.record_success("h")
    assert reg.is_blacklisted("h")
    assert reg.cooldown_for("h") == 0.0


def test_worker_state_registry_cooldown_expiry_readmits():
    reg = WorkerStateRegistry(failure_threshold=1, cooldown_secs=0.1)
    assert reg.record_failure("h")
    assert reg.is_blacklisted("h")
    time.sleep(0.15)
    assert not reg.is_blacklisted("h")
    assert reg.blacklisted_hosts() == []
    # The failure streak reset with the expiry: the host must re-earn
    # the threshold before it is blacklisted again.
    reg2 = WorkerStateRegistry(failure_threshold=2, cooldown_secs=0.1)
    reg2.record_failure("h")
    assert reg2.record_failure("h")
    time.sleep(0.15)
    assert not reg2.is_blacklisted("h")
    assert not reg2.record_failure("h")  # 1/2 again, not 3/2


def test_worker_state_registry_reblacklist_doubles_cooldown():
    reg = WorkerStateRegistry(failure_threshold=1, cooldown_secs=10.0)
    assert reg.record_failure("h")
    assert reg.cooldown_for("h") == 10.0
    # Force expiry without sleeping: age the entry past the cooldown.
    for expected in (20.0, 40.0, 80.0, 160.0, 160.0):  # capped at 16x
        reg._blacklist["h"] = time.monotonic() - 10 * 160.0
        assert not reg.is_blacklisted("h")  # expired -> readmitted
        assert reg.record_failure("h")      # repeat failure
        assert reg.cooldown_for("h") == expected
    # A straggler exiting 0 while the host is STILL blacklisted must
    # not weaken the doubled cooldown (or clear the streak).
    reg.record_success("h")
    assert reg.cooldown_for("h") == 160.0
    # A recorded success after readmission resets the doubling.
    reg._blacklist.pop("h")
    reg.record_success("h")
    assert reg.cooldown_for("h") == 10.0


def test_worker_state_registry_from_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_HOST_FAILURE_THRESHOLD", "3")
    monkeypatch.setenv("HOROVOD_BLACKLIST_COOLDOWN", "42.5")
    reg = WorkerStateRegistry.from_env()
    assert reg._threshold == 3
    assert reg._cooldown == 42.5
    # Explicit arguments win over the env.
    reg = WorkerStateRegistry.from_env(failure_threshold=1,
                                       cooldown_secs=0.0)
    assert reg._threshold == 1 and reg._cooldown == 0.0
    # Malformed env degrades to the defaults, not a crash.
    monkeypatch.setenv("HOROVOD_HOST_FAILURE_THRESHOLD", "lots")
    monkeypatch.setenv("HOROVOD_BLACKLIST_COOLDOWN", "soon")
    reg = WorkerStateRegistry.from_env()
    assert reg._threshold == 1 and reg._cooldown == 0.0


def test_discovery_script_timeout_is_transient(tmp_path, monkeypatch):
    from horovod_tpu.elastic.discovery import DiscoveryFailure
    script = tmp_path / "disc.sh"
    script.write_text("#!/bin/sh\nsleep 30\n")
    script.chmod(0o755)
    # Constructor argument.
    disc = HostDiscoveryScript(str(script), timeout=0.2)
    with pytest.raises(DiscoveryFailure):
        disc.find_available_hosts_and_slots()
    # Env wiring (HOROVOD_DISCOVERY_SCRIPT_TIMEOUT) when no argument.
    monkeypatch.setenv("HOROVOD_DISCOVERY_SCRIPT_TIMEOUT", "0.2")
    disc = HostDiscoveryScript(str(script))
    with pytest.raises(DiscoveryFailure):
        disc.find_available_hosts_and_slots()


def test_discovery_script_nonzero_rc_is_transient(tmp_path):
    from horovod_tpu.elastic.discovery import DiscoveryFailure
    script = tmp_path / "disc.sh"
    script.write_text("#!/bin/sh\nexit 7\n")
    script.chmod(0o755)
    with pytest.raises(DiscoveryFailure):
        HostDiscoveryScript(str(script)).find_available_hosts_and_slots()


def test_discovery_script_malformed_slots_skipped(tmp_path):
    # One bad line must not kill the whole pass (it used to raise
    # ValueError and lose the tick): skip it, keep the good hosts.
    script = tmp_path / "disc.sh"
    script.write_text("#!/bin/sh\necho host1:4\necho host2:abc\n"
                      "echo host3\n")
    script.chmod(0o755)
    disc = HostDiscoveryScript(str(script), default_slots=2)
    assert disc.find_available_hosts_and_slots() == {
        "host1": 4, "host3": 2}


class _FlakyDiscovery(FixedHosts):
    """FixedHosts that raises DiscoveryFailure while ``failing``."""

    def __init__(self, hosts):
        super().__init__(hosts)
        self.failing = False

    def find_available_hosts_and_slots(self):
        from horovod_tpu.elastic.discovery import DiscoveryFailure
        if self.failing:
            raise DiscoveryFailure("flaking")
        return super().find_available_hosts_and_slots()


def _make_driver(discovery, **kwargs):
    from horovod_tpu.elastic.driver import ElasticDriver
    return ElasticDriver(["true"], discovery, min_np=1, max_np=None,
                         **kwargs)


def _close_driver(driver):
    # The constructor binds both server sockets without starting their
    # serve loops; close the sockets directly (stop() would block on a
    # shutdown handshake the never-started loop cannot answer).
    driver._server._server.server_close()
    driver._kv._httpd.server_close()


def test_discovery_failure_streak_tolerance_and_escalation():
    disc = _FlakyDiscovery({"a": 1})
    driver = _make_driver(disc, discovery_failure_threshold=3)
    reasons = []
    driver._recompute_world = reasons.append
    try:
        driver._discovery_tick()
        assert driver._hosts.current_hosts == {"a": 1}
        assert reasons == ["discovery update"]
        # Failures below the threshold keep the last good view.
        disc.failing = True
        driver._discovery_tick()
        driver._discovery_tick()
        assert driver._hosts.current_hosts == {"a": 1}
        assert reasons == ["discovery update"]
        # The threshold-th consecutive failure escalates: the view is
        # invalidated and the world recomputes onto the below-min_np
        # fail-fast deadline.
        driver._discovery_tick()
        assert driver._hosts.current_hosts == {}
        assert reasons == ["discovery update", "discovery escalation"]
        # Recovery after escalation re-forms the world.
        disc.failing = False
        driver._discovery_tick()
        assert driver._hosts.current_hosts == {"a": 1}
        assert driver._discovery_failures == 0
        assert reasons[-1] == "discovery update"
    finally:
        _close_driver(driver)


def test_discovery_success_resets_failure_streak():
    disc = _FlakyDiscovery({"a": 1})
    driver = _make_driver(disc, discovery_failure_threshold=3)
    driver._recompute_world = lambda reason: None
    try:
        driver._discovery_tick()
        disc.failing = True
        driver._discovery_tick()
        driver._discovery_tick()
        disc.failing = False
        driver._discovery_tick()  # streak broken
        assert driver._discovery_failures == 0
        disc.failing = True
        driver._discovery_tick()
        driver._discovery_tick()
        # 2 < 3: the earlier near-miss streak must not carry over.
        assert driver._hosts.current_hosts == {"a": 1}
    finally:
        _close_driver(driver)


def test_respawn_backoff_grows_and_caps():
    driver = _make_driver(FixedHosts({"127.0.0.1": 1}),
                          respawn_backoff_base=0.02,
                          respawn_backoff_cap=0.08)
    driver._make_worker_proc = lambda slot, env: None  # carrier declines
    slot = ("127.0.0.1", 0)
    try:
        driver._target = [slot]
        backoffs = []
        for _ in range(4):
            time.sleep(0.1)  # > cap: every call is an eligible attempt
            driver._check_procs()
            backoffs.append(driver._spawn_backoff[slot])
        assert backoffs == [0.04, 0.08, 0.08, 0.08]
    finally:
        _close_driver(driver)


def test_elastic_sampler_shard_and_resume():
    s = ElasticSampler(dataset_size=10, shuffle=False)
    # Uninitialized world -> single rank sees everything.
    assert sorted(s) == list(range(10))
    s.record_indices([0, 1, 2, 3])
    s.on_reset()
    assert sorted(s) == [4, 5, 6, 7, 8, 9]
    sd = s.state_dict()
    s2 = ElasticSampler(dataset_size=10, shuffle=False)
    s2.load_state_dict(sd)
    assert sorted(s2) == [4, 5, 6, 7, 8, 9]
    s2.set_epoch(1)
    assert len(s2) == 10


def test_object_state_commit_restore():
    st = ObjectState(batch=0, lr=0.1)
    st.batch = 5
    st.commit()
    st.batch = 9
    st.lr = 0.5
    st.restore()
    assert st.batch == 5 and st.lr == 0.1


def test_commit_id_monotonic_and_restore_preserves_it():
    st = ObjectState(batch=0)
    assert st._commit_id == 0  # construction is not a commit
    st.commit()
    st.commit()
    assert st._commit_id == 2
    st.batch = 99
    st.restore()
    # restore rolls the DATA back to commit 2; the id stays (the
    # restored state IS commit 2, not a new one).
    assert st._commit_id == 2 and st.batch == 0


@pytest.mark.parametrize("mine,peers", [(False, True), (True, False),
                                        (False, False)])
def test_members_leave_at_the_same_commit(monkeypatch, mine, peers):
    """A host update that has reached any member takes every member out at
    the same commit: the flag goes through one Max allreduce, so a member
    the driver's notice has not reached yet leaves with the one it has."""
    from horovod_tpu.common import basics
    from horovod_tpu.elastic import state as state_mod
    from horovod_tpu.elastic.worker import HostsUpdatedInterrupt
    from horovod_tpu.ops import api

    class Notices:
        active = True
        pending = 7 if mine else None

        def has_update(self):
            return self.pending is not None

        def consume_update(self):
            self.pending = None

        def drain_requested(self):
            return False

    nm, calls = Notices(), []

    def allreduce(flag, op=None, name=None):
        calls.append((int(flag[0]), op, name))
        return np.maximum(flag, int(peers))

    monkeypatch.setattr(state_mod, "notification_manager", lambda: nm)
    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "size", lambda: 2)
    monkeypatch.setattr(api, "allreduce", allreduce)
    st = ObjectState(batch=0)
    if mine or peers:
        with pytest.raises(HostsUpdatedInterrupt):
            st.check_host_updates()
    else:
        st.check_host_updates()
    assert calls == [(int(mine), api.MAX, "elastic.hosts_updated")]
    assert not nm.has_update()


def test_port_base_lies_under_the_kernels_own_range():
    """The block a world's ranks bind seconds later is drawn where no
    outgoing connection and no bind to port 0 can take a port of it."""
    import socket

    from horovod_tpu.runner import util
    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        floor = int(f.read().split()[0])
    for size in (1, 3, 8):
        base = util.find_free_port_base(size)
        assert 1024 <= base and base + size + 201 + size < floor
        for port in range(base, base + size):
            s = socket.socket()
            s.bind(("127.0.0.1", port))
            s.close()


# -- durable spills (ISSUE 5 tentpole layer 3) -----------------------------

def test_spill_roundtrip_keep_k_and_corrupt_fallback(tmp_path, monkeypatch):
    from horovod_tpu.elastic import spill
    monkeypatch.setenv("HOROVOD_STATE_SPILL_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_STATE_KEEP", "3")
    for cid in range(1, 6):
        spill.write(cid, b"payload-%d" % cid, "r0")
    names = sorted(os.listdir(str(tmp_path)))
    assert len(names) == 3, names  # keep-last-K pruned commits 1 and 2
    assert not [n for n in names if n.startswith(".tmp")]
    assert spill.load_newest() == (5, b"payload-5")
    # Torn tail on the newest: restore falls back to the previous blob.
    newest = [p for c, p in spill.scan() if c == 5][0]
    blob = open(newest, "rb").read()
    with open(newest, "wb") as f:
        f.write(blob[:-3])
    assert spill.load_newest() == (4, b"payload-4")
    # Bit flip inside the payload: the CRC catches it.
    p4 = [p for c, p in spill.scan() if c == 4][0]
    raw = bytearray(open(p4, "rb").read())
    raw[-1] ^= 0xFF
    with open(p4, "wb") as f:
        f.write(bytes(raw))
    assert spill.load_newest() == (3, b"payload-3")
    # Nothing strictly newer than memory -> no adoption.
    assert spill.load_newest(min_commit_id=3) is None
    assert spill.have_evidence()


def test_spill_fault_injection_torn_write(tmp_path, monkeypatch):
    """elastic.state.spill drop = the write lands truncated mid-payload
    (a host losing power mid-commit); restore must detect and skip it."""
    from horovod_tpu.common import faultline
    from horovod_tpu.elastic import spill
    monkeypatch.setenv("HOROVOD_STATE_SPILL_DIR", str(tmp_path))
    spill.write(1, b"A" * 64, "r0")
    monkeypatch.setenv("HVD_TPU_FAULT", "elastic.state.spill:drop@times=1")
    faultline.reset()
    try:
        spill.write(2, b"B" * 64, "r0")
    finally:
        monkeypatch.delenv("HVD_TPU_FAULT")
        faultline.reset()
    assert len(spill.scan()) == 2  # the torn file exists on disk ...
    assert spill.load_newest() == (1, b"A" * 64)  # ... and is skipped


def test_spill_prune_sweeps_stale_tmp_files(tmp_path, monkeypatch):
    # A crash between mkstemp and os.replace leaves a temp file; the
    # pruner sweeps it once it is safely past any live write's
    # lifetime, and never touches a fresh (possibly in-flight) one.
    from horovod_tpu.elastic import spill
    monkeypatch.setenv("HOROVOD_STATE_SPILL_DIR", str(tmp_path))
    stale = tmp_path / ".tmp-spill-dead"
    stale.write_bytes(b"x")
    old = time.time() - 600
    os.utime(str(stale), (old, old))
    fresh = tmp_path / ".tmp-spill-live"
    fresh.write_bytes(b"y")
    spill.write(1, b"payload", "r0")
    assert not stale.exists()
    assert fresh.exists()


def test_replica_buddies_prefer_other_hosts(monkeypatch):
    # A replica on the source's own host dies with it; host-distinct
    # slots must be picked first.
    from horovod_tpu.elastic import driver as driver_mod
    sent = []
    monkeypatch.setattr(
        driver_mod, "send_message",
        lambda addr, secret, payload, **kw: sent.append(addr))
    d = _make_driver(FixedHosts({"a": 2, "b": 1}))
    try:
        d._target = [("a", 0), ("a", 1), ("b", 0)]
        for slot, addr in [(("a", 0), ("a", 1)), (("a", 1), ("a", 2)),
                           (("b", 0), ("b", 3))]:
            d._worker_addrs.register(slot, addr)
        resp = d._handle({"kind": "replicate", "host": "a", "slot": 0,
                          "commit_id": 5, "replicas": 1, "blob": b"x"})
        assert resp["delivered"] == 1
        assert sent == [("b", 3)]  # not the same-host slot ("a", 2)
    finally:
        _close_driver(d)


def test_sync_restores_from_spill_uninitialized_world(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("HOROVOD_STATE_SPILL_DIR", str(tmp_path))
    st = ObjectState(batch=0, total=0.0)
    st.batch, st.total = 4, 8.0
    st.commit()
    # A fresh incarnation (full-job restart) adopts the newest blob.
    st2 = ObjectState(batch=0, total=0.0)
    st2.sync()
    assert st2.batch == 4 and st2.total == 8.0
    assert st2._commit_id == 1


def test_sync_no_valid_blob_fails_loudly(tmp_path, monkeypatch):
    from horovod_tpu.elastic.state import StateSyncError
    monkeypatch.setenv("HOROVOD_STATE_SPILL_DIR", str(tmp_path))
    corrupt = tmp_path / "state-00000000000000000003-r0.spill"
    corrupt.write_bytes(b"garbage that is definitely not a spill blob")
    st = ObjectState(batch=0)
    with pytest.raises(StateSyncError):
        st.sync()
    # An EMPTY spill dir is a genuine fresh start, never an error.
    corrupt.unlink()
    st.sync()
    assert st.batch == 0


def test_jax_state_spill_roundtrip(tmp_path, monkeypatch):
    from horovod_tpu.elastic.state import JaxState
    monkeypatch.setenv("HOROVOD_STATE_SPILL_DIR", str(tmp_path))
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    st = JaxState(params=params, epoch=0)
    st.params = {"w": st.params["w"] + 2.5}
    st.epoch = 3
    st.commit()
    st2 = JaxState(params={"w": np.zeros((2, 3), np.float32)}, epoch=0)
    st2.sync()
    assert st2.epoch == 3 and st2._commit_id == 1
    np.testing.assert_array_equal(
        np.asarray(st2.params["w"]),
        np.arange(6, dtype=np.float32).reshape(2, 3) + 2.5)


# -- survivor-elected state root (ISSUE 5 tentpole layer 2) ----------------

def test_elect_state_root_prefers_progress_then_low_rank(monkeypatch):
    from horovod_tpu.jax import functions
    recs = [{"rank": 0, "commit_id": 0, "evidence": False},
            {"rank": 1, "commit_id": 7, "evidence": False},
            {"rank": 2, "commit_id": 7, "evidence": False}]
    monkeypatch.setattr(functions, "allgather_object",
                        lambda obj, name=None: recs)
    root, records = functions.elect_state_root(recs[0])
    assert root["rank"] == 1  # max progress, ties to the LOWEST rank
    assert records is recs
    # All blank (fresh world): degenerates to the reference's rank 0.
    recs0 = [{"rank": r, "commit_id": 0} for r in (2, 0, 1)]
    monkeypatch.setattr(functions, "allgather_object",
                        lambda obj, name=None: recs0)
    root, _ = functions.elect_state_root(recs0[0])
    assert root["rank"] == 0


# -- drain protocol bookkeeping (ISSUE 5 tentpole layer 1) -----------------

class _FakeProc:
    def __init__(self, rc):
        self._rc = rc
        self.terminated = False

    def poll(self):
        return self._rc

    def terminate(self):
        self.terminated = True


def test_drained_worker_is_planned_removal_not_failure():
    """Satellite: a drained (or clean-exit-0) worker resets the slot's
    respawn backoff and never contributes to
    HOROVOD_HOST_FAILURE_THRESHOLD — no blacklist, no respawn churn."""
    from horovod_tpu.elastic.worker import DRAIN_EXIT_CODE
    driver = _make_driver(FixedHosts({"h": 1}), failure_threshold=1)
    driver._make_worker_proc = lambda slot, env: None
    slot = ("h", 0)
    recomputes = []
    driver._recompute_world = recomputes.append
    try:
        driver._target = [slot]
        driver._published = True
        # (1) rc fallback: the drain notice was lost, the distinguished
        # exit code alone marks the removal as planned.
        driver._spawn_backoff[slot] = 8.0
        driver._procs[slot] = _FakeProc(DRAIN_EXIT_CODE)
        driver._spawn_attempts[slot] = time.monotonic()
        assert driver._check_procs() is False
        assert driver._registry.blacklisted_hosts() == []
        assert driver._registry._failures == {}
        assert slot not in driver._spawn_backoff  # backoff reset
        assert slot not in driver._succeeded      # but not "done" either
        assert recomputes == ["worker drained"]
        # (2) notice path: after a drain message ANY rc is planned
        # (SIGKILL beat the clean exit).
        resp = driver._handle({"kind": "drain", "host": "h", "slot": 0,
                               "commit_id": 3, "reason": "preemption"})
        assert resp.get("ok"), resp
        driver._procs[slot] = _FakeProc(137)
        driver._spawn_attempts[slot] = time.monotonic()
        assert driver._check_procs() is False
        assert driver._registry.blacklisted_hosts() == []
        assert driver._registry._failures == {}
        assert recomputes == ["worker drained", "worker drained"]
        assert slot not in driver._draining  # consumed by the reap
        # (3) clean exit 0 resets the backoff too and counts as done.
        driver._spawn_backoff[slot] = 8.0
        driver._procs[slot] = _FakeProc(0)
        driver._spawn_attempts[slot] = time.monotonic()
        assert driver._check_procs() is True  # all target slots done
        assert slot not in driver._spawn_backoff
        # (4) an actual failure still counts toward the threshold.
        driver._succeeded.discard(slot)
        driver._procs[slot] = _FakeProc(17)
        driver._spawn_attempts[slot] = time.monotonic()
        driver._check_procs()
        assert driver._registry.blacklisted_hosts() == ["h"]
    finally:
        _close_driver(driver)


def test_drained_slot_not_respawned_in_same_reap_pass():
    """Regression (found live by the straggler-drain e2e): the reap
    pass that books a drain runs its spawn list BEFORE the epoch-bump
    recompute, so a same-pass respawn of the drained slot could
    rendezvous into the still-PUBLISHED stale epoch, resolve the OLD
    world's jax coordinator, and FATAL the survivors mid-recovery
    (new-incarnation connect propagated by error polling).  The
    drained slot must sit out its own reap pass — the failure path
    already does, via failed_hosts — and respawn only after the world
    recompute, where the fresh worker parks on "wait" until the new
    epoch publishes."""
    from horovod_tpu.elastic.worker import DRAIN_EXIT_CODE
    driver = _make_driver(FixedHosts({"h": 1}))
    slot = ("h", 0)
    spawned = []
    driver._spawn_workers = lambda slots: spawned.extend(slots)
    recomputes = []
    driver._recompute_world = recomputes.append
    try:
        driver._target = [slot]
        driver._published = True
        driver._procs[slot] = _FakeProc(DRAIN_EXIT_CODE)
        # No spawn-attempt stamp: without the drained-slot exclusion
        # the throttle alone would happily respawn in this very pass.
        assert driver._check_procs() is False
        assert spawned == []                      # sat out its pass
        assert recomputes == ["worker drained"]   # epoch bump booked
        # The NEXT pass (post-recompute world) respawns it normally.
        assert driver._check_procs() is False
        assert spawned == [slot]
    finally:
        _close_driver(driver)


def test_drain_ack_drop_falls_back_to_exit_code(monkeypatch):
    """driver.drain.ack drop: the notice is lost at the driver; the
    slot is NOT marked draining, but the drain exit code still lands
    the worker in the planned-removal path."""
    from horovod_tpu.common import faultline
    from horovod_tpu.elastic.worker import DRAIN_EXIT_CODE
    monkeypatch.setenv("HVD_TPU_FAULT", "driver.drain.ack:drop")
    faultline.reset()
    driver = _make_driver(FixedHosts({"h": 1}))
    driver._make_worker_proc = lambda slot, env: None
    driver._recompute_world = lambda reason: None
    slot = ("h", 0)
    try:
        driver._target = [slot]
        resp = driver._handle({"kind": "drain", "host": "h", "slot": 0,
                               "commit_id": 3, "reason": "preemption"})
        assert "error" in resp
        assert slot not in driver._draining
        driver._procs[slot] = _FakeProc(DRAIN_EXIT_CODE)
        driver._spawn_attempts[slot] = time.monotonic()
        driver._check_procs()
        assert driver._registry.blacklisted_hosts() == []
    finally:
        monkeypatch.delenv("HVD_TPU_FAULT")
        faultline.reset()
        _close_driver(driver)


def test_stall_error_aborts_via_drain_path(monkeypatch):
    """Satellite: a StallError (HOROVOD_STALL_SHUTDOWN_TIME_SECONDS
    crossed) leaves through the drain protocol — committed-then-abort
    with the distinguished exit code — not a hard crash that would
    blacklist the healthy host that merely watched a peer die."""
    import horovod_tpu.elastic.worker as worker_mod
    from horovod_tpu.elastic import state as state_mod
    from horovod_tpu.elastic.worker import WorkerDrained
    from horovod_tpu.ops.engine import HorovodInternalError
    from horovod_tpu.utils.stall_inspector import StallError
    monkeypatch.setattr(worker_mod, "_manager", None)  # fresh singleton
    monkeypatch.setenv("HOROVOD_PREEMPT_GRACE_SECS", "0")  # no timer
    st = ObjectState(batch=2)
    st.commit()
    st.batch = 9  # half-applied step the abort must roll back
    # The engine wraps handle errors in HorovodInternalError with the
    # original as __cause__ (CollectiveHandle.wait raises `from`).
    cause = StallError("tensor 'b3' stalled beyond the threshold")
    exc = HorovodInternalError(str(cause))
    exc.__cause__ = cause
    with pytest.raises(WorkerDrained) as ei:
        state_mod._stall_abort(st, exc)
    assert ei.value.code == worker_mod.DRAIN_EXIT_CODE
    assert worker_mod.notification_manager().drain_requested()
    assert st.batch == 2  # restored to the last commit before aborting


def test_stall_abort_detection_covers_both_planes():
    # In-process engine: StallError chained as __cause__.  Native
    # core: Aborted status text only (operations.cc).  Anything else
    # stays on the restore-and-rejoin path.
    from horovod_tpu.elastic.state import _is_stall_abort
    from horovod_tpu.ops.engine import HorovodInternalError
    from horovod_tpu.utils.stall_inspector import StallError
    chained = HorovodInternalError("collective 'b3' failed")
    chained.__cause__ = StallError("stalled")
    assert _is_stall_abort(chained)
    assert _is_stall_abort(
        HorovodInternalError("stall shutdown threshold exceeded"))
    assert not _is_stall_abort(HorovodInternalError("peer closed"))


class _FakeMetadata:
    """GCE-style metadata server: worker-network-endpoints +
    unhealthy-workers, both mutable by the test."""

    def __init__(self):
        import http.server

        self.values = {"worker-network-endpoints": "",
                       "unhealthy-workers": None}
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - http.server API
                key = self.path.rsplit("/", 1)[-1]
                val = outer.values.get(key)
                if (val is None
                        or not self.path.startswith(
                            "/computeMetadata/v1/instance/attributes/")):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = val.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # noqa: D102 - silence
                pass

        self.server = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), Handler)
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    @property
    def url(self):
        return "http://127.0.0.1:%d/computeMetadata/v1" % \
            self.server.server_address[1]

    def stop(self):
        self.server.shutdown()


def test_tpu_slice_discovery_parsing():
    from horovod_tpu.elastic.discovery import TpuSliceDiscovery
    md = _FakeMetadata()
    try:
        # TPU VM triple form, host:port form, bare-host form.
        md.values["worker-network-endpoints"] = (
            "t1v-n-x-w-0:8470:10.0.0.1, 10.0.0.2:8470,10.0.0.3")
        disc = TpuSliceDiscovery(base_url=md.url, slots_per_host=4)
        assert disc.find_available_hosts_and_slots() == {
            "10.0.0.1": 4, "10.0.0.2": 4, "10.0.0.3": 4}
        # A preemption notice removes the host before it dies; the
        # missing unhealthy-workers attribute (404) means none.
        md.values["unhealthy-workers"] = "10.0.0.2"
        assert disc.find_available_hosts_and_slots() == {
            "10.0.0.1": 4, "10.0.0.3": 4}
    finally:
        md.stop()


# -- integration: real local worker processes ------------------------------

def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HOROVOD_RANK", None)
    env.pop("HOROVOD_ELASTIC_DRIVER_ADDR", None)
    return env


WORKER_COMMON = """
import os, sys, time
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()
state = elastic.ObjectState(batch=0, total=0.0)
"""


def test_elastic_fixed_world_completes(tmp_path):
    """Static elastic run: 2 workers, commits every batch, clean finish."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
@elastic.run
def train(state):
    while state.batch < 5:
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.total += float(np.asarray(out)[0])
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d total=%.1f"
          % (hvd.rank(), hvd.size(), state.total), flush=True)
    return state.total

train(state)
""")
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--min-np", "2", "--max-np", "2",
         sys.executable, str(script)],
        timeout=90, env=_env(), cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DONE rank=0 size=2 total=10.0" in proc.stdout
    assert "DONE rank=1 size=2 total=10.0" in proc.stdout


def test_elastic_worker_failure_blacklist_and_resume(tmp_path):
    """A worker dies mid-training: its host is blacklisted, the survivor
    restores the last commit and finishes alone (reference fault
    injection: kill a real worker process)."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
@elastic.run
def train(state):
    while state.batch < 8:
        if (os.environ.get("HOROVOD_HOSTNAME") == "127.0.0.2"
                and state.batch == 3):
            os._exit(17)  # simulated hardware failure
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.total += float(np.asarray(out)[0])
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d batch=%d"
          % (hvd.rank(), hvd.size(), state.batch), flush=True)

train(state)
""")
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "1",
         sys.executable, str(script)],
        timeout=90, env=_env(), cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # Survivor finished the epoch alone after the resize.
    assert "DONE rank=0 size=1 batch=8" in proc.stdout


def test_elastic_scale_up_mid_run(tmp_path):
    """Discovery output gains a host mid-run: workers re-rendezvous into
    the larger world and the joiner syncs state (reference: discovery
    script output mutated mid-test)."""
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text("127.0.0.1:2\n")
    disc = tmp_path / "disc.sh"
    disc.write_text("#!/bin/sh\ncat %s\n" % hosts_file)
    disc.chmod(0o755)
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
state.extra = 0

@elastic.run
def train(state):
    while hvd.size() < 3 or state.extra < 3:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.batch += 1
        if hvd.size() >= 3:
            state.extra += 1
        time.sleep(0.05)
        state.commit()
    print("DONE rank=%d size=%d" % (hvd.rank(), hvd.size()), flush=True)

train(state)
""")

    def add_host_later():
        time.sleep(12.0)
        hosts_file.write_text("127.0.0.1:2\n127.0.0.2:1\n")

    t = threading.Thread(target=add_host_later, daemon=True)
    t.start()
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "--host-discovery-script", str(disc),
         "--min-np", "2", "--max-np", "4",
         sys.executable, str(script)],
        timeout=200, env=_env(), cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(3):
        assert "DONE rank=%d size=3" % r in proc.stdout, proc.stdout


def test_elastic_multihost_resize(tmp_path):
    """Elastic scale-up of a MULTIHOST (device-payload) world: on the
    epoch change every worker leaves the global JAX runtime
    (jax.distributed shutdown), re-rendezvouses, and rejoins the
    resized runtime; device collectives flow in both worlds (closes
    the r2 gap: elastic was only exercised on the tcp plane)."""
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text("127.0.0.1:1\n127.0.0.2:1\n")
    disc = tmp_path / "disc.sh"
    disc.write_text("#!/bin/sh\ncat %s\n" % hosts_file)
    disc.chmod(0o755)
    started = tmp_path / "started"
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
state.extra = 0

@elastic.run
def train(state):
    while hvd.size() < 3 or state.extra < 2:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        assert float(np.asarray(out)[0]) == float(hvd.size())
        state.batch += 1
        if state.batch == 3 and hvd.rank() == 0:
            open("@STARTED@", "w").close()  # initial world is training
        if hvd.size() >= 3:
            state.extra += 1
        time.sleep(0.05)
        state.commit()
    print("DONE rank=%d size=%d" % (hvd.rank(), hvd.size()), flush=True)

train(state)
""".replace("@STARTED@", str(started)))

    def add_host_when_started():
        # Progress-triggered (not a fixed delay): under full-suite load
        # on one core the initial world can take >15s to even start.
        deadline = time.time() + 240
        while not started.exists() and time.time() < deadline:
            time.sleep(0.5)
        time.sleep(1.0)
        hosts_file.write_text(
            "127.0.0.1:1\n127.0.0.2:1\n127.0.0.3:1\n")

    t = threading.Thread(target=add_host_when_started, daemon=True)
    t.start()
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "--multihost",
         "--host-discovery-script", str(disc),
         "--min-np", "2", "--max-np", "3",
         sys.executable, str(script)],
        timeout=180, env=_env(), cwd=REPO)      # 12-30 s alone
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(3):
        assert "DONE rank=%d size=3" % r in proc.stdout, proc.stdout


def test_elastic_multihost_watchdog_recovery(tmp_path):
    """Elastic x multihost x execution watchdog, integrated (VERDICT r4
    Next #8): a member wedges MID-BURST with the pipeline window full —
    it negotiates the burst's groups but never dispatches its side of
    the compiled programs (the undetectable-on-ICI failure), stays
    alive past the watchdog window, then dies.  The survivor must
    (1) fail the in-flight handles loudly via the device-exec watchdog,
    (2) let the elastic machinery blacklist the dead host and resize,
    (3) resume from the last commit on the new world and finish."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
BURST = 4

@elastic.run
def train(state):
    while state.batch < 6:
        doomed = (hvd.size() > 1 and state.batch == 2
                  and os.environ.get("HOROVOD_HOSTNAME") == "127.0.0.2")
        if doomed:
            # Negotiate the burst (control plane sees this rank ready)
            # but never dispatch the device programs; stay alive so
            # the transport looks healthy, then die.
            from horovod_tpu.common import basics
            eng = basics._get_mh_engine()
            eng._execute = lambda g: None
            for i in range(BURST):
                hvd.allreduce_async(np.ones(4, np.float32), op=hvd.Sum,
                                    name="b%d.%d" % (state.batch, i))
            time.sleep(40)
            os._exit(17)
        hs = [hvd.allreduce_async(np.ones(4, np.float32), op=hvd.Sum,
                                  name="b%d.%d" % (state.batch, i))
              for i in range(BURST)]
        try:
            vals = [float(np.asarray(h.wait(120)).reshape(-1)[0])
                    for h in hs]
        except Exception as exc:
            if "watchdog" in str(exc):
                print("WATCHDOG_SEEN rank=%d batch=%d"
                      % (hvd.rank(), state.batch), flush=True)
            raise
        assert vals[0] == float(hvd.size()), vals
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d batch=%d"
          % (hvd.rank(), hvd.size(), state.batch), flush=True)

train(state)
""")
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "--multihost",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "1",
         sys.executable, str(script)],
        timeout=300,
        env=dict(_env(), **{
            "HOROVOD_DEVICE_EXEC_TIMEOUT_SECONDS": "8",
            "HOROVOD_MAX_INFLIGHT_GROUPS": "4",
        }), cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # The survivor saw the watchdog diagnostic (not a transport error:
    # the wedged member was alive when the timeout fired) ...
    assert "WATCHDOG_SEEN rank=0 batch=2" in proc.stdout, proc.stdout
    # ... and resumed from the commit on the shrunken world.
    assert "DONE rank=0 size=1 batch=6" in proc.stdout, proc.stdout


@pytest.mark.slow
def test_elastic_multihost_deadline_expiry_restores_from_commit(tmp_path):
    """ISSUE 18 acceptance: elastic x multihost x per-collective
    deadline, integrated.  At batch 2 every worker arms
    ``mh.deadline.wedge`` (once per process): the next negotiated group
    is registered and deadline-stamped but its dispatch is withheld —
    a program that never starts.  The 8 s deadline must expire it, the
    engine poisons with the RESTORE-shaped CollectiveDeadlineExceeded
    (never the drain-shaped stall text), and the elastic loop restores
    every worker from the last commit IN-PROCESS: the world stays size
    2, training resumes at batch 2, and the final total proves zero
    committed steps were lost or double-counted."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
ARMED = {"done": False}

@elastic.run
def train(state):
    while state.batch < 6:
        if state.batch == 2 and not ARMED["done"]:
            # Same SPMD point on every rank; the process-global flag
            # keeps the post-restore replay of batch 2 from re-arming.
            ARMED["done"] = True
            os.environ["HVD_TPU_FAULT"] = "mh.deadline.wedge:drop@times=1"
            from horovod_tpu.common import faultline
            faultline.reset()
        try:
            out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                                name="b%d" % state.batch)
        except Exception as exc:
            assert "stall shutdown threshold" not in str(exc), exc
            if "deadline" in str(exc):
                print("DEADLINE_SEEN rank=%d batch=%d"
                      % (hvd.rank(), state.batch), flush=True)
            raise
        state.total += float(np.asarray(out)[0])
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d batch=%d total=%.1f"
          % (hvd.rank(), hvd.size(), state.batch, state.total),
          flush=True)

train(state)
""")
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "--multihost",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "1",
         sys.executable, str(script)],
        timeout=300,
        env=dict(_env(), **{
            "HOROVOD_COLLECTIVE_TIMEOUT_SECS": "8",
        }), cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # Both workers hit the expiry (the wedge armed on every rank) ...
    assert "DEADLINE_SEEN rank=0 batch=2" in proc.stdout, proc.stdout
    assert "DEADLINE_SEEN rank=1 batch=2" in proc.stdout, proc.stdout
    # ... and BOTH survived the restore: same processes, full-size
    # world, resumed from the batch-2 commit with an exact total
    # (2.0 per batch x 6 batches — nothing lost, nothing replayed).
    assert "DONE rank=0 size=2 batch=6 total=12.0" in proc.stdout, \
        proc.stdout
    assert "DONE rank=1 size=2 batch=6 total=12.0" in proc.stdout, \
        proc.stdout
    # The drain-shaped abort never fired anywhere in the world.
    assert "stall shutdown threshold" not in proc.stdout + proc.stderr


def test_tpu_discovery_preemption_resizes_world(tmp_path):
    """A preemption notice appears on the fake TPU metadata server
    mid-run: the driver drops the host from the slice view, the doomed
    worker is stopped, and the survivor re-rendezvouses into a smaller
    world and finishes from committed state (SURVEY §5: control-plane
    preemption notices play the discovery-script role)."""
    md = _FakeMetadata()
    md.values["worker-network-endpoints"] = (
        "w0:8470:127.0.0.1,w1:8470:127.0.0.2")
    started = tmp_path / "started"
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
state.extra = 0

@elastic.run
def train(state):
    while hvd.size() > 1 or state.extra < 3:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.batch += 1
        if state.batch == 3 and hvd.rank() == 0:
            open("@STARTED@", "w").close()  # 2-rank world is training
        if hvd.size() == 1:
            state.extra += 1
        time.sleep(0.05)
        state.commit()
    print("DONE rank=%d size=%d batch=%d"
          % (hvd.rank(), hvd.size(), state.batch), flush=True)

train(state)
""".replace("@STARTED@", str(started)))

    def preempt_when_started():
        # Progress-triggered, not a fixed delay (see the resize test).
        deadline = time.time() + 240
        while not started.exists() and time.time() < deadline:
            time.sleep(0.5)
        time.sleep(1.0)
        md.values["unhealthy-workers"] = "127.0.0.2"

    t = threading.Thread(target=preempt_when_started, daemon=True)
    t.start()
    env = _env()
    env["HVD_TPU_METADATA_URL"] = md.url
    try:
        proc = run_world(
            [sys.executable, "-m", "horovod_tpu.runner",
             "--tpu-discovery", "--min-np", "1", "--max-np", "2",
             sys.executable, str(script)],
            timeout=100, env=env,
            cwd=REPO)
    finally:
        md.stop()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DONE rank=0 size=1" in proc.stdout, proc.stdout


def test_elastic_die_injection_recovery(tmp_path):
    """The worker-kill recovery scenario driven by the fault plane
    instead of a hand-written os._exit: HVD_TPU_FAULT arms a `die` at
    the commit seam, conditioned on the victim host, so EVERY worker
    runs identical user code and the injection env alone picks the
    casualty.  The driver must reap the rc, blacklist the host, and
    the survivor must restore from commit and finish alone."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
@elastic.run
def train(state):
    while state.batch < 6:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.total += float(np.asarray(out)[0])
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d batch=%d"
          % (hvd.rank(), hvd.size(), state.batch), flush=True)

train(state)
""")
    env = _env()
    env["HVD_TPU_FAULT"] = "elastic.state.commit:die:21@host=127.0.0.2"
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "1",
         sys.executable, str(script)],
        timeout=90,
        env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DONE rank=0 size=1 batch=6" in proc.stdout, proc.stdout


def test_elastic_blacklist_cooldown_rejoin(tmp_path):
    """Blacklist cooldown, end to end: a die-injected host is
    blacklisted, the survivor resumes alone, the cooldown expires, the
    host re-enters discovery, its worker respawns and rejoins via the
    normal re-rendezvous, and the run finishes with the FULL world.
    The injection fires only in world epoch 1 (@epoch=1), so the
    respawned worker on the same host proves recovery, not death."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
state.extra = 0

@elastic.run
def train(state):
    while hvd.size() < 2 or state.extra < 3:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.batch += 1
        if hvd.size() >= 2:
            state.extra += 1
        time.sleep(0.05)
        state.commit()
    print("DONE rank=%d size=%d" % (hvd.rank(), hvd.size()), flush=True)

train(state)
""")
    env = _env()
    env["HVD_TPU_FAULT"] = \
        "elastic.state.commit:die:21@host=127.0.0.2@epoch=1"
    env["HOROVOD_BLACKLIST_COOLDOWN"] = "3"
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "1",
         "--max-np", "2",
         sys.executable, str(script)],
        timeout=140,
        env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # The host was blacklisted with a cooldown, expired, and rejoined:
    # BOTH ranks finish in a size-2 world.
    for r in range(2):
        assert "DONE rank=%d size=2" % r in proc.stdout, \
            proc.stdout + proc.stderr
    assert "blacklisting host 127.0.0.2" in proc.stderr, proc.stderr
    assert "cooldown" in proc.stderr, proc.stderr


def test_elastic_discovery_flake_recovery(tmp_path):
    """A bounded discovery-flake window (drop @after=2 @times=2, under
    the default HOROVOD_DISCOVERY_FAILURE_THRESHOLD=3) is absorbed on
    the last good host view: the world never changes and the run
    completes cleanly."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
@elastic.run
def train(state):
    while state.batch < 40:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.batch += 1
        time.sleep(0.05)
        state.commit()
    print("DONE rank=%d size=%d batch=%d"
          % (hvd.rank(), hvd.size(), state.batch), flush=True)

train(state)
""")
    env = _env()
    env["HVD_TPU_FAULT"] = "elastic.discovery.run:drop@after=2@times=2"
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         sys.executable, str(script)],
        timeout=100,
        env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(2):
        assert "DONE rank=%d size=2 batch=40" % r in proc.stdout, \
            proc.stdout + proc.stderr
    assert "keeping last good host view" in proc.stderr, proc.stderr


def test_elastic_discovery_escalation_fails_fast(tmp_path):
    """The escalation boundary: discovery fails PERSISTENTLY (drop with
    no @times bound), the failure streak crosses the threshold, the
    driver discards the host view, and the run dies LOUDLY via the
    elastic below-min_np deadline — no hang, no indefinite training on
    a stale world view."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
@elastic.run
def train(state):
    while True:
        hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                      name="b%d" % state.batch)
        state.batch += 1
        time.sleep(0.05)
        state.commit()

train(state)
""")
    env = _env()
    env["HVD_TPU_FAULT"] = "elastic.discovery.run:drop@after=4"
    env["HOROVOD_ELASTIC_EXIT_GRACE"] = "5"
    t0 = time.monotonic()
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         "--elastic-timeout", "6",
         sys.executable, str(script)],
        timeout=140,
        env=env, cwd=REPO)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "escalating" in proc.stderr, proc.stderr
    assert "below min_np" in proc.stderr, proc.stderr
    assert time.monotonic() - t0 < scaled_timeout(120)


def test_elastic_spawn_drop_respawn_backoff_recovers(tmp_path):
    """driver.spawn.attempt drop: both initial spawn attempts are
    declined by injection; the reap loop's exponential respawn backoff
    retries them and the world still forms and finishes."""
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
@elastic.run
def train(state):
    while state.batch < 3:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d" % (hvd.rank(), hvd.size()), flush=True)

train(state)
""")
    env = _env()
    env["HVD_TPU_FAULT"] = "driver.spawn.attempt:drop@times=2"
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         sys.executable, str(script)],
        timeout=100,
        env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for r in range(2):
        assert "DONE rank=%d size=2" % r in proc.stdout, \
            proc.stdout + proc.stderr
    assert "dropped (faultline driver.spawn.attempt)" in proc.stderr, \
        proc.stderr


DRAIN_WORKER = """
import hashlib, os, sys, time
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()
state = elastic.ObjectState(batch=0, params=np.zeros(8, np.float32))

@elastic.run
def train(state):
    print("SYNCED rank=%d batch=%d commit=%d root=%s"
          % (hvd.rank(), state.batch, state._commit_id,
             state._sync_root), flush=True)
    while state.batch < 8:
        out = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.params = state.params + np.asarray(out)
        state.batch += 1
        state.commit()
    digest = hashlib.md5(np.asarray(state.params,
                                    np.float32).tobytes()).hexdigest()
    print("DONE rank=%d size=%d batch=%d params=%s"
          % (hvd.rank(), hvd.size(), state.batch, digest), flush=True)

train(state)
"""


def test_elastic_preemption_drain_survivor_elected_root(tmp_path):
    """ISSUE 5 acceptance: injected preemption (worker.preempt.sigterm)
    on the rank-0 host mid-epoch → the worker finishes the in-flight
    step, commits, sends an acked drain notice, and exits with the
    drain code; the driver treats it as a PLANNED removal (no
    blacklist, no failure count); the respawned blank worker must NOT
    win the root election — the survivor (max commit id) does, and the
    restored params are bitwise-identical on all ranks."""
    script = tmp_path / "train.py"
    script.write_text(DRAIN_WORKER)
    env = _env()
    # Fires on the 3rd commit of the epoch-1 worker on 127.0.0.1 (the
    # rank-0 host): mid-epoch, after real progress exists.  The
    # respawned worker runs in epoch >= 2, so the injection never
    # re-fires and the world proves recovery.
    env["HVD_TPU_FAULT"] = \
        "worker.preempt.sigterm:drop@host=127.0.0.1@epoch=1@after=2@times=1"
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "1",
         "--max-np", "2",
         sys.executable, str(script)],
        timeout=160,
        env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # Drain sequence: worker announced it, driver acked and treated
    # the exit as planned ...
    assert "draining at commit 3" in proc.stderr, proc.stderr
    assert "planned removal" in proc.stderr, proc.stderr
    # ... with NO blacklist entry (the whole point: preemption is not
    # a host failure).
    assert "blacklisting host" not in proc.stderr, proc.stderr
    # The respawned blank worker (rank 0 again: first host in target
    # order) adopted the SURVIVOR's progress via the elected root —
    # commit id 3, root rank 1, not a zero-filled restart.
    assert "SYNCED rank=0 batch=3 commit=3 root=1" in proc.stdout, \
        proc.stdout + proc.stderr
    # Both ranks finished the epoch with bitwise-identical params.
    digests = {line.split("params=")[1].strip()
               for line in proc.stdout.splitlines()
               if "DONE rank=" in line and "batch=8" in line}
    done = [line for line in proc.stdout.splitlines()
            if "DONE rank=" in line]
    assert len(done) == 2 and len(digests) == 1, \
        proc.stdout + proc.stderr


SPILL_WORKER = """
import os, sys, time
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()
state = elastic.ObjectState(batch=0, total=0.0)

@elastic.run
def train(state):
    print("ENTER rank=%d batch=%d commit=%d"
          % (hvd.rank(), state.batch, state._commit_id), flush=True)
    while state.batch < 6:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.total += float(np.asarray(out)[0])
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d batch=%d total=%.1f"
          % (hvd.rank(), hvd.size(), state.batch, state.total),
          flush=True)

train(state)
"""


def test_elastic_full_restart_restores_from_spill(tmp_path):
    """ISSUE 5 acceptance: EVERY worker dies at once (whole-job
    preemption) with durable spills on; a fresh run over the same
    spill dir restores from the newest VALID blob — the newest blob
    itself was torn by injection (elastic.state.spill), so restore
    falls back to the previous commit.  Run 1: commits 1-5 spill (#5
    torn), all workers die at commit 6.  Run 2: resumes at commit 4."""
    spill_dir = tmp_path / "spills"
    script = tmp_path / "train.py"
    script.write_text(SPILL_WORKER)
    env = _env()
    env["HOROVOD_STATE_SPILL_DIR"] = str(spill_dir)
    env1 = dict(env)
    env1["HVD_TPU_FAULT"] = ("elastic.state.spill:drop@after=4@times=1,"
                             "elastic.state.commit:die:21@after=5")
    env1["HOROVOD_ELASTIC_EXIT_GRACE"] = "5"
    proc1 = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         "--elastic-timeout", "6",
         sys.executable, str(script)],
        timeout=150,
        env=env1, cwd=REPO)
    # Multi-host loss: the whole run fails (both hosts die at commit 6).
    assert proc1.returncode != 0, proc1.stdout + proc1.stderr
    from horovod_tpu.elastic import spill
    on_disk = spill.scan(str(spill_dir))
    assert on_disk and max(c for c, _ in on_disk) == 5, on_disk
    # Run 2: fresh job, same spill dir, no faults.  Commit 5's blob is
    # torn on disk -> restore falls back to commit 4 and finishes.
    proc2 = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         sys.executable, str(script)],
        timeout=150,
        env=env, cwd=REPO)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "skipping corrupt spill" in proc2.stderr, proc2.stderr
    for r in range(2):
        assert "ENTER rank=%d batch=4 commit=4" % r in proc2.stdout, \
            proc2.stdout + proc2.stderr
        # total: 4 restored batches x 2.0 + 2 fresh batches x 2.0
        assert "DONE rank=%d size=2 batch=6 total=12.0" % r \
            in proc2.stdout, proc2.stdout + proc2.stderr


def test_elastic_unformable_world_worker_deadline(tmp_path):
    """ISSUE 2 acceptance: a permanently-unformable world leaves NO
    worker alive past HOROVOD_ELASTIC_TIMEOUT + eps.  The driver is
    SIGKILLed (no cleanup) and one worker SIGKILLed, so the survivor's
    collective fails and its rejoin faces an unreachable driver
    forever.  Pre-fix the rejoin retry loop reset its clock around a
    hardcoded 600 s deadline (workers observed alive 13x past the
    env); post-fix ONE monotonic deadline spans every retry and a
    last-resort os._exit covers a wedged teardown."""
    import signal

    timeout_s = 6.0
    script = tmp_path / "train.py"
    script.write_text(WORKER_COMMON + """
print("WORKER_PID %d %s" % (
    os.getpid(), os.environ.get("HOROVOD_HOSTNAME", "?")), flush=True)

@elastic.run
def train(state):
    while True:
        hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                      name="b%d" % state.batch)
        state.batch += 1
        if state.batch == 3:
            print("TRAINING %d" % hvd.rank(), flush=True)
        time.sleep(0.05)
        state.commit()

train(state)
""")
    env = _env()
    env["HOROVOD_ELASTIC_EXIT_GRACE"] = "5"
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         "--elastic-timeout", str(timeout_s),
         sys.executable, str(script)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO, start_new_session=True)

    pids = {}        # host -> worker pid
    training = set()
    lines = []

    def read_output():
        for line in iter(proc.stdout.readline, ""):
            lines.append(line)
            if "WORKER_PID" in line:
                tail = line.split("WORKER_PID", 1)[1].split()
                pids[tail[1]] = int(tail[0])
            if "TRAINING" in line:
                training.add(line.split("TRAINING", 1)[1].split()[0])

    t = threading.Thread(target=read_output, daemon=True)
    t.start()

    def alive(pid):
        try:
            os.kill(pid, 0)
            return True
        except OSError:
            return False

    survivor = None
    try:
        deadline = time.monotonic() + scaled_timeout(90)
        while (len(pids) < 2 or len(training) < 2) \
                and time.monotonic() < deadline:
            assert proc.poll() is None, "".join(lines)
            time.sleep(0.2)
        assert len(pids) == 2 and len(training) == 2, "".join(lines)
        survivor, victim = pids["127.0.0.1"], pids["127.0.0.2"]
        # Driver dies uncleanly (no worker teardown), then the peer:
        # the survivor is on its own with an unreachable driver.
        os.kill(proc.pid, signal.SIGKILL)
        os.kill(victim, signal.SIGKILL)
        t0 = time.monotonic()
        budget = scaled_timeout(timeout_s + 5 + 15)  # timeout+grace+eps
        while alive(survivor) and time.monotonic() - t0 < budget:
            time.sleep(0.25)
        gone_after = time.monotonic() - t0
        assert not alive(survivor), (
            "survivor pid %d still alive %.1fs after the world became "
            "unformable (HOROVOD_ELASTIC_TIMEOUT=%s):\n%s"
            % (survivor, gone_after, timeout_s, "".join(lines)))
    finally:
        for pid in list(pids.values()) + [proc.pid]:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except OSError:
            pass
        proc.wait(timeout=30)


SHARD_SPILL_WORKER = """
import hashlib, os, sys
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.common import metrics

hvd.init()
rng = np.random.RandomState(7)
state = elastic.JaxState(
    params={"w": rng.randn(64, 8).astype(np.float32),
            "b": rng.randn(64).astype(np.float64)},
    batch=0)


def state_hash(state):
    h = hashlib.sha256()
    for k in sorted(state.params):
        h.update(np.ascontiguousarray(
            np.asarray(state.params[k])).tobytes())
    return h.hexdigest()[:16]


@elastic.run
def train(state):
    print("ENTER rank=%d size=%d batch=%d commit=%d hash=%s"
          % (hvd.rank(), hvd.size(), state.batch, state._commit_id,
             state_hash(state)), flush=True)
    print("RESTORE_BYTES rank=%d bytes=%d"
          % (hvd.rank(),
             int(metrics.series_sum("shardspill_restore_bytes_total"))),
          flush=True)
    while state.batch < 6:
        out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                            name="b%d" % state.batch)
        state.params["w"] = state.params["w"] + float(np.asarray(out)[0])
        state.batch += 1
        state.commit()
        print("COMMIT rank=%d commit=%d hash=%s"
              % (hvd.rank(), state._commit_id, state_hash(state)),
              flush=True)
    print("DONE rank=%d size=%d batch=%d hash=%s"
          % (hvd.rank(), hvd.size(), state.batch, state_hash(state)),
          flush=True)


train(state)
"""


@pytest.mark.slow
def test_shard_spill_n_to_m_restore(tmp_path):
    """ISSUE 15 acceptance: a 2-proc world's SHARDED commit restores
    bitwise-identical state into a 1-proc world (2→1) AND a 3-proc
    world (2→3), per-host restore I/O < full-state size in the 3-proc
    world, and a torn shard (elastic.state.shard@shard=1@rank=0 —
    rank 0's buddy copy, the one the reader tries FIRST) falls back
    per shard to the surviving copy without discarding the commit."""
    import shutil

    spill_dir = tmp_path / "spills"
    script = tmp_path / "train.py"
    script.write_text(SHARD_SPILL_WORKER)
    env = _env()
    env["HOROVOD_STATE_SPILL_DIR"] = str(spill_dir)
    env["HOROVOD_STATE_SHARD_SPILL"] = "1"

    # Run 1: 2 writers, commits 1..5 land sharded (rank 0's copy of
    # shard 1 torn every commit), every worker dies at commit 6.
    env1 = dict(env)
    env1["HVD_TPU_FAULT"] = ("elastic.state.shard:drop@shard=1@rank=0,"
                             "elastic.state.commit:die:21@after=5")
    env1["HOROVOD_ELASTIC_EXIT_GRACE"] = "5"
    proc1 = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         "--elastic-timeout", "6",
         sys.executable, str(script)],
        timeout=300,
        env=env1, cwd=REPO)
    assert proc1.returncode != 0, proc1.stdout + proc1.stderr
    assert "torn (faultline elastic.state.shard)" in proc1.stderr, \
        proc1.stderr
    import re as _re
    h5 = set(_re.findall(r"COMMIT rank=\d+ commit=5 hash=(\w+)",
                         proc1.stdout))
    assert len(h5) == 1, proc1.stdout  # ranks agree at commit 5
    h5 = h5.pop()
    from horovod_tpu.elastic import shardspill
    manifest = shardspill.load_manifest(5, d=str(spill_dir))
    assert manifest is not None and manifest["n_shards"] == 2
    total = int(manifest["total_bytes"])

    # Freeze the durable state for the second reader world: each run
    # appends its own commits.
    dir_b = tmp_path / "spills_b"
    shutil.copytree(spill_dir, dir_b)

    # Run 2a: 2 -> 1 resharding restore (whole stream, one reader).
    proc2 = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1", "--min-np", "1",
         sys.executable, str(script)],
        timeout=300,
        env=env, cwd=REPO)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "ENTER rank=0 size=1 batch=5 commit=5 hash=%s" % h5 \
        in proc2.stdout, proc2.stdout + proc2.stderr
    assert "falling back to the next copy of shard 1" in proc2.stderr, \
        proc2.stderr

    # Run 2b: 2 -> 3 resharding restore (streamed ranges + collective
    # reassembly; per-host restore I/O asserted < full state).
    env_b = dict(env)
    env_b["HOROVOD_STATE_SPILL_DIR"] = str(dir_b)
    proc3 = run_world(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1,127.0.0.3:1", "--min-np", "3",
         sys.executable, str(script)],
        timeout=300,
        env=env_b, cwd=REPO)
    assert proc3.returncode == 0, proc3.stdout + proc3.stderr
    for r in range(3):
        assert "ENTER rank=%d size=3 batch=5 commit=5 hash=%s" \
            % (r, h5) in proc3.stdout, proc3.stdout + proc3.stderr
    streamed = {m.group(1): int(m.group(2)) for m in _re.finditer(
        r"RESTORE_BYTES rank=(\d+) bytes=(\d+)", proc3.stdout)}
    assert len(streamed) == 3, proc3.stdout
    # Per-host peak restore I/O strictly under full-state size; the
    # union still covers the whole stream (readers 0/1 own one source
    # shard each, reader 2 owns none in the 2→3 case).
    assert all(v < total for v in streamed.values()), (streamed, total)
    assert sum(streamed.values()) >= total, (streamed, total)


# -- HA control plane: KV failover + driver crash adoption (ISSUE 17) ------

HA_KV_WORKER = """
import os, sys, time
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.runner.http_client import RendezvousClient

hvd.init()
state = elastic.ObjectState(batch=0)

@elastic.run
def train(state):
    # External HA KV pair via HOROVOD_RENDEZVOUS_ENDPOINTS (no addr,
    # no secret: the out-of-process kv_server runs unauthenticated).
    cli = RendezvousClient()
    while state.batch < 20:
        hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                      name="b%d" % state.batch)
        state.batch += 1
        state.commit()
        print("STEP rank=%d batch=%d" % (hvd.rank(), state.batch),
              flush=True)
        if state.batch == 10:
            # Park mid-run on the HA KV: the leader is SIGKILLed while
            # every worker polls this key, so finishing at all proves
            # get_blocking re-resolves its endpoint per iteration.
            cli.put("step10/%d" % hvd.rank(), "here")
            cli.get_blocking("go2", timeout=120.0)
    print("DONE rank=%d size=%d batch=%d"
          % (hvd.rank(), hvd.size(), state.batch), flush=True)

train(state)
"""


def _start_kv_server(env, args):
    """Spawn ``python -m horovod_tpu.runner.kv_server`` and parse its
    liveness line; returns (proc, port)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.kv_server"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    line = ""
    for line in iter(proc.stdout.readline, ""):
        if "KV_SERVER LISTENING" in line:
            break
    assert "KV_SERVER LISTENING" in line, line
    port = int(line.split("port=")[1].split()[0])
    # Drain further output so the pipe never fills.
    threading.Thread(target=lambda: [None for _ in
                                     iter(proc.stdout.readline, "")],
                     daemon=True).start()
    return proc, port


def _control_get(port, path):
    import json
    import urllib.request
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=5) as resp:
        return json.loads(resp.read().decode())


@pytest.mark.slow
def test_control_plane_failover_e2e(tmp_path):
    """ISSUE 17 headline: SIGKILL the active KV server while a 2-proc
    elastic run is parked on it mid-training.  The warm standby takes
    over within the lease at a bumped term, every worker fails over
    to it mid-poll, NO training step is lost (each rank runs batches
    1..20 exactly once), no blacklist churn, and the recovered store
    is bitwise-identical to the pre-kill leader snapshot."""
    import signal

    kv_env = _env()
    kv_env.pop("HOROVOD_SECRET_KEY", None)
    kv_env["HOROVOD_CONTROL_LEASE_SECS"] = "1.0"
    leader_proc, lport = _start_kv_server(
        kv_env, ["--host", "127.0.0.1", "--journal-dir",
                 str(tmp_path / "kv-a")])
    standby_proc, sport = _start_kv_server(
        kv_env, ["--host", "127.0.0.1", "--journal-dir",
                 str(tmp_path / "kv-b"),
                 "--standby-of", "127.0.0.1:%d" % lport])

    script = tmp_path / "train.py"
    script.write_text(HA_KV_WORKER)
    env = _env()
    env["HOROVOD_RENDEZVOUS_ENDPOINTS"] = \
        "127.0.0.1:%d,127.0.0.1:%d" % (lport, sport)
    run = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "2",
         sys.executable, str(script)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO)
    try:
        from horovod_tpu.runner.http_client import RendezvousClient
        cli = RendezvousClient(
            endpoints=["127.0.0.1:%d" % lport, "127.0.0.1:%d" % sport])
        # Phase 1 done: both ranks at batch 10, parked on "go2".
        cli.get_blocking("step10/0", timeout=scaled_timeout(180))
        cli.get_blocking("step10/1", timeout=scaled_timeout(180))
        pre_kill = _control_get(lport, "/control/dump")
        # Wait for full replication, then SIGKILL the leader.
        deadline = time.monotonic() + scaled_timeout(30)
        while time.monotonic() < deadline:
            if _control_get(sport, "/control/dump")["kv"] \
                    == pre_kill["kv"]:
                break
            time.sleep(0.1)
        leader_proc.send_signal(signal.SIGKILL)
        leader_proc.wait(timeout=10)
        # Standby promotes within the lease, at a bumped term ...
        deadline = time.monotonic() + scaled_timeout(30)
        status = {}
        while time.monotonic() < deadline:
            status = _control_get(sport, "/control/status")
            if status["role"] == "leader":
                break
            time.sleep(0.1)
        assert status.get("role") == "leader", status
        assert status["term"] >= 2, status
        # ... with the recovered store bitwise-identical to the
        # pre-kill leader snapshot.
        post = _control_get(sport, "/control/dump")
        assert post["kv"] == pre_kill["kv"]
        assert post["seq"] >= pre_kill["seq"]
        # Release phase 2 through the NEW leader (the client rotates
        # past the dead one).
        cli.put("go2", "now")
        out, err = run.communicate(timeout=scaled_timeout(240))
        assert run.returncode == 0, out + err
        # Zero lost steps: each rank ran batches 1..20 exactly once
        # (a re-rendezvous/rollback would repeat a batch number).
        for r in range(2):
            # The runner prefixes forwarded worker lines with
            # "[host:slot]<stdout>", so match by substring.
            batches = [int(line.split("batch=")[1])
                       for line in out.splitlines()
                       if "STEP rank=%d " % r in line]
            assert batches == list(range(1, 21)), (r, batches)
            assert "DONE rank=%d size=2 batch=20" % r in out, out + err
        # ... and no blacklist churn: the failover was invisible to
        # the membership plane.
        assert "blacklisting host" not in err, err
    finally:
        for p in (run, leader_proc, standby_proc):
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)


def test_driver_adoption_restores_world(tmp_path, monkeypatch):
    """Driver crash adoption: a restarted driver pointed at the same
    control journal reconstructs secret/epoch/assignments/blacklist,
    reattaches the still-live workers WITHOUT a world re-formation
    (epoch preserved, no respawn), and books their clean finishes via
    the `finished` notice (no proc handle exists to reap)."""
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner import journal as control_journal
    from horovod_tpu.runner.services import MessageServer

    jdir = str(tmp_path / "ctl")
    slots = [("127.0.0.1", 0), ("127.0.0.1", 1)]

    d1 = ElasticDriver(["true"], FixedHosts({"127.0.0.1": 2}),
                       min_np=2, max_np=2, journal_dir=jdir)
    secret, msg_port = d1._secret, d1._server.port

    # Fake live workers: notification services that answer pings with
    # the journaled secret (what a real WorkerNotificationManager runs).
    fakes = [MessageServer(lambda req: {"ok": True}, secret)
             for _ in slots]
    addrs = {}
    for slot, f in zip(slots, fakes):
        addrs[slot] = ("127.0.0.1", f.start())

    # Publish a world by hand (no real spawns), journal it, crash.
    with d1._lock:
        d1._epoch = 3
        d1._target = list(slots)
        d1._assignments = {s: {"rank": i} for i, s in enumerate(slots)}
        d1._published = True
        d1._port_base = 29600
    for slot, addr in addrs.items():
        d1._worker_addrs.register(slot, addr)
    d1._registry.record_failure("10.9.9.9")  # journaled blacklist
    d1._journal_control()
    _close_driver(d1)
    d1._kv._httpd.journal.close()

    # The restarted driver adopts: journaled secret + message port
    # (workers hold both), old epoch, restored blacklist, external
    # (no-proc-handle) worker bookkeeping.
    monkeypatch.setenv("HOROVOD_CONTROL_RECOVERY_DEADLINE", "15")
    d2 = ElasticDriver(["true"], FixedHosts({"127.0.0.1": 2}),
                       min_np=2, max_np=2, journal_dir=jdir)
    try:
        assert d2._secret == secret
        assert d2._server.port == msg_port
        assert d2._adopt_rec is not None
        assert d2._try_adopt()
        assert d2._epoch == 3 and d2._published
        assert d2._target == slots
        assert set(d2._external) == set(slots)
        assert d2._registry.is_blacklisted("10.9.9.9")
        assert d2._assignments[slots[1]]["rank"] == 1

        # Clean finishes arrive as `finished` notices; the run is then
        # complete with rc=0 and the epoch never bumped.
        for slot in slots:
            resp = d2._handle({"kind": "finished", "host": slot[0],
                               "slot": slot[1], "commit_id": 7})
            assert resp == {"ok": True}
        assert not d2._external
        assert d2._check_procs() is True
        assert d2._rc == 0 and d2._epoch == 3
    finally:
        _close_driver(d2)
        d2._kv._httpd.journal.close()
        for f in fakes:
            f.stop()


def test_driver_adoption_fails_loudly_when_workers_gone(tmp_path,
                                                        monkeypatch):
    """Past HOROVOD_CONTROL_RECOVERY_DEADLINE with a journaled worker
    unreachable, adoption aborts (control_adopt_failed) and the driver
    falls back to ordinary world formation — it must NOT adopt a
    half-dead world silently."""
    from horovod_tpu.common import metrics
    from horovod_tpu.elastic.driver import ElasticDriver

    jdir = str(tmp_path / "ctl")
    d1 = ElasticDriver(["true"], FixedHosts({"127.0.0.1": 1}),
                       min_np=1, max_np=1, journal_dir=jdir)
    with d1._lock:
        d1._epoch = 2
        d1._target = [("127.0.0.1", 0)]
        d1._assignments = {("127.0.0.1", 0): {"rank": 0}}
        d1._published = True
    # A dead notification address: nothing listens there anymore.
    import socket as _socket
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()
    d1._worker_addrs.register(("127.0.0.1", 0),
                              ("127.0.0.1", dead_port))
    d1._journal_control()
    _close_driver(d1)
    d1._kv._httpd.journal.close()

    monkeypatch.setenv("HOROVOD_CONTROL_RECOVERY_DEADLINE", "0.5")
    d2 = ElasticDriver(["true"], FixedHosts({"127.0.0.1": 1}),
                       min_np=1, max_np=1, journal_dir=jdir)
    try:
        t0 = time.monotonic()
        assert d2._try_adopt() is False
        assert time.monotonic() - t0 < 10.0
        assert not d2._published and d2._epoch == 0
        # The stale journaled address was purged: re-formation starts
        # from a clean notification table.
        assert d2._worker_addrs.get(("127.0.0.1", 0)) is None
    finally:
        _close_driver(d2)
        d2._kv._httpd.journal.close()
