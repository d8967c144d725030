"""Elastic driver: discovery-driven world management + re-rendezvous.

Reference parity: ``horovod/runner/elastic/driver.py`` (ElasticDriver),
``rendezvous.py`` and the elastic half of ``gloo_run.py``: a background
discovery thread polls the host-discovery script; on host add/remove or
worker failure the driver bumps the world epoch, notifies workers (who
raise ``HostsUpdatedInterrupt``), blacklists failed hosts
(``registration.py``), recomputes slot→rank assignments within
[min_np, max_np], and serves the new assignment to each worker's
re-rendezvous poll.  Payload bootstrap (the TcpCore address table) goes
through the same RendezvousServer KV store as the static launcher,
reset at each epoch.
"""

from __future__ import annotations

import logging
import socket
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import json

from ..common import faultline, metrics, skew
from ..common.envutil import env_int
from ..runner import journal as control_journal
from ..runner import safe_shell_exec, util
from ..runner.http_server import RendezvousServer
from ..runner.services import AddressTable, MessageServer, send_message
from .discovery import (FixedHosts, HostDiscovery, HostDiscoveryScript,
                        HostManager, HostUpdateResult)
from .registration import WorkerStateRegistry
from .worker import DRAIN_EXIT_CODE

LOG = logging.getLogger("horovod_tpu.elastic.driver")

Slot = Tuple[str, int]

DEFAULT_DISCOVERY_FAILURE_THRESHOLD = 3


def _discovery_failure_threshold_from_env() -> int:
    """Consecutive discovery failures the driver absorbs on the last
    good host view before escalating: HOROVOD_DISCOVERY_FAILURE_THRESHOLD
    (default 3).  One read point."""
    return env_int("HOROVOD_DISCOVERY_FAILURE_THRESHOLD",
                   DEFAULT_DISCOVERY_FAILURE_THRESHOLD, minimum=1)


class ElasticDriver:
    def __init__(self, command: List[str], discovery: HostDiscovery,
                 min_np: int, max_np: Optional[int],
                 env: Optional[Dict[str, str]] = None,
                 elastic_timeout: float = 600.0,
                 discovery_interval: float = 1.0,
                 failure_threshold: Optional[int] = None,
                 blacklist_cooldown: Optional[float] = None,
                 discovery_failure_threshold: Optional[int] = None,
                 start_timeout: float = 120.0,
                 ssh_port: int = 22,
                 respawn_backoff_base: float = 1.0,
                 respawn_backoff_cap: float = 30.0,
                 tenant_id: Optional[str] = None,
                 tenant_priority: Optional[int] = None,
                 journal_dir: Optional[str] = None):
        self.command = command
        self.min_np = max(1, min_np)  # graftlint: guarded-by=_lock
        self.max_np = max_np  # graftlint: guarded-by=_lock
        self.env = dict(env or {})
        # Multi-tenant pods (elastic/scheduler.py): this driver manages
        # ONE tenant's world.  The id is exported to the workers
        # (HOROVOD_TENANT_ID — it scopes their KV namespace, spill
        # subdirectory, and faultline @tenant= targeting) and labels
        # this driver's metric series so several tenant drivers in one
        # scheduler process never collapse into one series.
        self.tenant_id = tenant_id
        self.tenant_priority = tenant_priority
        self._mlabels = {"tenant": tenant_id} if tenant_id else {}
        self.elastic_timeout = elastic_timeout
        self.discovery_interval = discovery_interval
        self.start_timeout = start_timeout
        self.ssh_port = ssh_port
        # Per-slot respawn throttle: exponential backoff between spawn
        # retries (carrier declined / spawn failed), so a slot that
        # cannot start does not hammer a struggling host at a fixed
        # rate.  Reset when a spawn succeeds.
        self.respawn_backoff_base = max(0.0, respawn_backoff_base)
        self.respawn_backoff_cap = max(self.respawn_backoff_base,
                                       respawn_backoff_cap)
        self.discovery_failure_threshold = (
            discovery_failure_threshold
            if discovery_failure_threshold is not None
            else _discovery_failure_threshold_from_env())

        # None = launcher env decides (HOROVOD_HOST_FAILURE_THRESHOLD /
        # HOROVOD_BLACKLIST_COOLDOWN); an explicit argument wins.
        self._registry = WorkerStateRegistry.from_env(
            failure_threshold=failure_threshold,
            cooldown_secs=blacklist_cooldown)
        self._extra_handler = None  # platform hook for extra msg kinds
        self._hosts = HostManager(discovery, self._registry.is_blacklisted)
        # HA control plane (runner/journal.py): with a journal dir the
        # KV store is write-ahead journaled and the driver journals its
        # own bookkeeping (the control record), so a restarted driver
        # can ADOPT the old world — same secret, same ports, same
        # epoch — instead of re-forming it.  An explicit journal_dir
        # wins over HOROVOD_CONTROL_JOURNAL_DIR (+ tenant subdir).
        self._journal_dir = (
            journal_dir if journal_dir is not None
            else control_journal.control_journal_dir(tenant_id))
        self._adopt_rec = control_journal.peek_control_record(
            self._journal_dir)
        self._secret = util.make_secret()
        msg_port = kv_port = 0
        if self._adopt_rec is not None:
            # The journaled secret MUST survive the restart: live
            # workers still HMAC with it, and the journaled ports are
            # the addresses baked into their environment.
            self._secret = self._adopt_rec.get("secret") or self._secret
            msg_port = int(self._adopt_rec.get("msg_port") or 0)
            kv_port = int(self._adopt_rec.get("kv_port") or 0)
        try:
            self._server = MessageServer(self._handle, self._secret,
                                         port=msg_port)
        except OSError as exc:
            # The old notification port is unavailable: workers hold it
            # in HOROVOD_ELASTIC_DRIVER_ADDR and could never reach this
            # incarnation — adoption is off the table.
            LOG.error("cannot rebind journaled driver port %d (%s): "
                      "abandoning crash adoption, re-forming the world",
                      msg_port, exc)
            self._adopt_rec = None
            self._secret = util.make_secret()
            kv_port = 0
            self._server = MessageServer(self._handle, self._secret)
        try:
            self._kv = RendezvousServer(secret=self._secret,
                                        port=kv_port,
                                        journal_dir=self._journal_dir)
        except OSError as exc:
            # A lost KV port only matters at the NEXT re-rendezvous
            # (workers learn the new address with their next
            # assignment); adoption of the live world can proceed.
            LOG.warning("cannot rebind journaled KV port %d (%s); "
                        "serving the KV on a fresh port", kv_port, exc)
            self._kv = RendezvousServer(secret=self._secret,
                                        journal_dir=self._journal_dir)
        # Fleet-wide scrape: GET /metrics on the rendezvous server
        # merges this driver's registry with every live worker's
        # snapshot (one rank label per source).
        self._kv.metrics_provider = self._metrics_text
        # Skew observatory (common/skew.py): the observe half of the
        # telemetry control loop.  The skew loop feeds it the same
        # worker snapshots the /metrics merge pulls; a sustained
        # straggler triggers the configured action — drain rides the
        # r10 planned-removal path, shrink goes through the pod
        # scheduler's hook (set by PodScheduler._make_driver on
        # tenant drivers).  GET /skew serves its state as JSON.
        self.scheduler_shrink = None  # set by the pod scheduler
        self._observatory = skew.SkewObservatory(
            drain_fn=self._straggler_drain,
            shrink_fn=self._straggler_shrink)
        self._kv.skew_provider = self._skew_text

        # World state below is shared between the run() reap loop
        # ("caller"), the discovery thread, and the message-server
        # thread (_handle) — every write goes through self._lock (an
        # RLock: _publish_epoch runs inside _handle_rendezvous's
        # critical section).
        self._lock = threading.RLock()
        self._epoch = 0  # graftlint: guarded-by=_lock
        self._target: List[Slot] = []  # graftlint: guarded-by=_lock
        self._ready: set = set()  # graftlint: guarded-by=_lock
        self._published = False  # graftlint: guarded-by=_lock
        self._assignments: Dict[Slot, Dict] = {}  # graftlint: guarded-by=_lock
        self._port_base = 0  # graftlint: guarded-by=_lock
        self._procs: Dict[Slot, safe_shell_exec.ManagedProcess] = {}  # graftlint: guarded-by=_lock
        # Generation-tracked so a reattached worker's fresh endpoint
        # always shadows a journal-restored (or leftover) one, never
        # the reverse (services.AddressTable; own internal lock).
        self._worker_addrs = AddressTable()
        # ADOPTED workers: slots whose live process belongs to a dead
        # driver incarnation (crash adoption) — no proc handle to
        # reap, so liveness is ping-based.  Value = consecutive ping
        # misses.
        self._external: Dict[Slot, int] = {}  # graftlint: guarded-by=_lock
        self._external_checked = 0.0  # reap-loop thread only
        # slots told/forced to stop; slots whose proc exited 0;
        # slots that announced a drain (planned removal — preemption,
        # stall abort); per-slot spawn retry throttle; spawn RPCs in
        # flight off-lock.
        self._stopped: set = set()  # graftlint: guarded-by=_lock
        self._succeeded: set = set()  # graftlint: guarded-by=_lock
        self._draining: set = set()  # graftlint: guarded-by=_lock
        self._spawn_attempts: Dict[Slot, float] = {}  # graftlint: guarded-by=_lock
        self._spawn_backoff: Dict[Slot, float] = {}  # graftlint: guarded-by=_lock
        self._pending_spawns: set = set()  # graftlint: guarded-by=_lock
        # Consecutive failed discovery passes; owned by the discovery
        # thread (run()'s startup loop finishes before it starts).
        self._discovery_failures = 0
        self._shutdown = threading.Event()
        self._below_min_since: Optional[float] = None  # graftlint: guarded-by=_lock
        # Scheduler hold: a preempted tenant's driver parks with an
        # EMPTY world on purpose — the below-min_np deadline must not
        # fail the run while the pod scheduler is holding its slots.
        self._held = False  # graftlint: guarded-by=_lock
        # Highest epoch a worker has demanded via min_epoch (its world
        # broke in a way the driver cannot observe); the discovery loop
        # rebuilds when it passes the current epoch.
        self._rebuild_wanted = 0  # graftlint: guarded-by=_lock
        self._rc = 0

    # -- message service ---------------------------------------------------

    def _handle(self, req: Dict) -> Dict:  # graftlint: thread=msg-server
        kind = req.get("kind")
        if kind == "register":
            slot = (req["host"], int(req["slot"]))
            # A live registration evicts any stale entry shadowing it
            # (same slot re-registering from a new port after failover,
            # or another slot's leftover claim on this address).
            self._worker_addrs.register(
                slot, (req["host"], int(req["port"])))
            self._journal_control()
            return {"ok": True}
        if kind == "finished":
            # An ADOPTED worker's only "done" signal: no proc handle
            # exists to reap its rc=0, so the clean return of its
            # train function reports here (worker.py send_finished).
            # Harmless duplicate for driver-owned procs — the reap
            # loop already books their exit.
            slot = (req["host"], int(req["slot"]))
            with self._lock:
                was_external = slot in self._external
                if was_external:
                    del self._external[slot]
                    self._succeeded.add(slot)
                    self._worker_addrs.purge(slot)
            if was_external:
                self._registry.record_success(slot[0])
                metrics.event("external_finished", host=slot[0],
                              slot=slot[1],
                              commit_id=req.get("commit_id"))
                LOG.info("adopted worker %s:%d finished cleanly",
                         slot[0], slot[1])
                self._journal_control()
            return {"ok": True}
        if kind == "rendezvous":
            return self._handle_rendezvous(
                (req["host"], int(req["slot"])),
                int(req.get("min_epoch", 0)))
        if kind == "drain":
            return self._handle_drain(
                (req["host"], int(req["slot"])),
                req.get("reason", "?"), int(req.get("commit_id", 0)))
        if kind == "replicate":
            return self._handle_replicate(req)
        if kind == "ping":
            return {"ok": True, "epoch": self._epoch}
        if self._extra_handler is not None:
            return self._extra_handler(req)
        return {"error": "unknown request %r" % kind}

    def _handle_rendezvous(self, slot: Slot, min_epoch: int = 0) -> Dict:
        with self._lock:
            if (self._shutdown.is_set() or slot in self._stopped
                    or self._registry.is_blacklisted(slot[0])):
                return {"status": "stop"}
            if min_epoch > self._epoch:
                # The worker's world broke in a way the driver cannot
                # observe (every process still alive: a transport
                # reset, a watchdog fire).  Its demand for a newer
                # epoch IS the world-change signal — record it; the
                # discovery loop re-forms the world (same membership
                # is fine, the new epoch is what re-bootstraps it).
                self._rebuild_wanted = max(self._rebuild_wanted,
                                           min_epoch)
                return {"status": "wait"}
            if not self._target:
                # Below min_np: hold workers until discovery refills the
                # world (their in-memory state survives the wait).
                return {"status": "wait"}
            if slot not in self._target:
                return {"status": "stop"}
            self._ready.add(slot)
            if not self._published and self._ready >= set(self._target):
                self._publish_epoch()
            if self._published and slot in self._assignments:
                return dict(self._assignments[slot], status="go")
            return {"status": "wait"}

    def _handle_drain(self, slot: Slot, reason: str,
                      commit_id: int) -> Dict:
        """A worker announced a PLANNED exit (preemption SIGTERM, stall
        abort): mark the slot draining so its exit is never treated as
        a failure — no blacklist, no failure count, no respawn-backoff
        penalty.  The distinguished drain exit code is the fallback
        signal when this notice (or its ack) is lost."""
        if faultline.site("driver.drain.ack"):
            LOG.warning("drain notice from %s:%d dropped (faultline "
                        "driver.drain.ack)", slot[0], slot[1])
            return {"error": "drain ack dropped (faultline "
                             "driver.drain.ack)"}
        with self._lock:
            self._draining.add(slot)
        metrics.event("drain_notice", host=slot[0], slot=slot[1],
                      reason=reason, commit_id=commit_id)
        LOG.warning("worker %s:%d draining (%s) at commit %d: planned "
                    "removal", slot[0], slot[1], reason, commit_id)
        return {"ok": True}

    def _handle_replicate(self, req: Dict) -> Dict:
        """Fan one worker's durable-commit blob out to its buddy ranks
        (the next k slots in target order): the driver owns the
        slot→address table, workers don't know their peers.  Runs on
        the message-server thread pool; sends are bounded and best-
        effort — replication must never wedge the control plane."""
        source = (req["host"], int(req["slot"]))
        want = max(0, int(req.get("replicas", 1)))
        with self._lock:
            target = list(self._target)
        addrs = self._worker_addrs.snapshot()
        if source not in target or want == 0:
            return {"ok": True, "delivered": 0}
        ring = target[target.index(source) + 1:] + \
            target[:target.index(source)]
        ring = [s for s in ring if s != source]
        # Host-distinct buddies first: a replica on the source's own
        # host dies with it in the host-loss scenario replication
        # exists for; same-host slots are only a last resort.
        buddies = ([s for s in ring if s[0] != source[0]]
                   + [s for s in ring if s[0] == source[0]])[:want]
        delivered = 0
        payload = {"kind": "replica", "commit_id": req.get("commit_id"),
                   "source_rank": req.get("source_rank"),
                   "blob": req.get("blob")}
        for buddy in buddies:
            addr = addrs.get(buddy)
            if addr is None:
                continue
            try:
                send_message(addr, self._secret, payload, timeout=5.0,
                             retries=0)
                delivered += 1
            except Exception:  # noqa: BLE001 — buddy may be mid-respawn
                LOG.debug("replica forward to %s:%d failed",
                          buddy[0], buddy[1], exc_info=True)
        return {"ok": True, "delivered": delivered}

    def _publish_epoch(self):  # graftlint: requires-lock=_lock
        """All target slots checked in: assign ranks and open the world
        (caller holds the lock)."""
        self._kv.reset()
        self._port_base = util.find_free_port_base(len(self._target))
        rendezvous_addr = "%s:%d" % (self._driver_host(), self._kv.port)
        hosts_in_order: List[str] = []
        for host, _ in self._target:
            if host not in hosts_in_order:
                hosts_in_order.append(host)
        local_sizes = {h: sum(1 for hh, _ in self._target if hh == h)
                       for h in hosts_in_order}
        self._assignments = {}
        rank = 0
        for cross_rank, host in enumerate(hosts_in_order):
            local_rank = 0
            for slot in [s for s in self._target if s[0] == host]:
                self._assignments[slot] = {
                    "epoch": self._epoch, "rank": rank,
                    "size": len(self._target),
                    "local_rank": local_rank,
                    "local_size": local_sizes[host],
                    "cross_rank": cross_rank,
                    "cross_size": len(hosts_in_order),
                    "port_base": self._port_base,
                    "rendezvous_addr": rendezvous_addr,
                }
                rank += 1
                local_rank += 1
        self._published = True
        metrics.gauge("elastic_epoch", **self._mlabels).set(self._epoch)
        metrics.event("epoch_published", epoch=self._epoch,
                      ranks=len(self._target),
                      hosts=len(hosts_in_order))
        LOG.info("epoch %d published: %d ranks over %d hosts",
                 self._epoch, len(self._target), len(hosts_in_order))
        self._journal_control()

    def _driver_host(self) -> str:
        if all(h == "localhost" or h.startswith("127.")
               for h, _ in self._target):
            return "127.0.0.1"
        try:
            return socket.gethostbyname(socket.gethostname())
        except socket.gaierror:
            return "127.0.0.1"

    # -- HA control plane: journaling + crash adoption ---------------------

    def _journal_control(self):
        """Persist this driver's bookkeeping as the journaled control
        record (runner/journal.py CONTROL_KEY): epoch, secret, ports,
        target, assignments, worker addresses, blacklist.  A restarted
        driver replays it in :meth:`_try_adopt`.  No-op without a
        journal directory."""
        if self._journal_dir is None:
            return
        with self._lock:
            rec = {
                "epoch": self._epoch,
                "secret": self._secret,
                "msg_port": self._server.port,
                "kv_port": self._kv.port,
                "port_base": self._port_base,
                "published": self._published,
                "target": [list(s) for s in self._target],
                "assignments": [[list(s), a] for s, a
                                in self._assignments.items()],
                "worker_addrs": [[list(s), list(a)] for s, a
                                 in self._worker_addrs.items()],
                "succeeded": [list(s) for s in self._succeeded],
                "blacklist": self._registry.blacklisted_hosts(),
                "tenant": self.tenant_id,
            }
            # Same lock order as _publish_epoch's _kv.reset(): driver
            # lock, then the KV httpd lock inside put_local.
            self._kv.put_local(control_journal.CONTROL_KEY,
                               json.dumps(rec, sort_keys=True).encode())

    def _try_adopt(self) -> bool:
        """Crash adoption: reconstruct the published world from the
        journaled control record and the live workers themselves.
        Every unfinished journaled slot must answer a ping within
        ``HOROVOD_CONTROL_RECOVERY_DEADLINE`` — then the old epoch is
        re-installed as-is (no epoch bump, no re-rendezvous) and those
        workers keep training as ADOPTED (external) slots.  Any
        journaled worker still missing at the deadline fails the
        adoption LOUDLY and the driver falls back to ordinary world
        re-formation, where the r2 elastic deadline governs."""
        rec = self._adopt_rec
        if not rec or not rec.get("published") or not rec.get("target"):
            return False
        budget = control_journal.recovery_deadline()
        deadline = time.monotonic() + budget
        target = [tuple(s) for s in rec["target"]]
        assignments = {tuple(s): a for s, a
                       in rec.get("assignments") or []}
        addrs = {tuple(s): tuple(a) for s, a
                 in rec.get("worker_addrs") or []}
        succeeded = {tuple(s) for s in rec.get("succeeded") or []}
        for host in rec.get("blacklist") or []:
            self._registry.restore_blacklist(host)
        for slot, addr in addrs.items():
            # Generation-0 seed: a live re-registration shadows it.
            self._worker_addrs.restore(slot, addr)
        want = [s for s in target if s not in succeeded]
        metrics.event("control_adopt_attempt", epoch=rec.get("epoch"),
                      slots=len(want), deadline_secs=budget)
        LOG.warning("journaled control record found (epoch %s, %d "
                    "unfinished slots): attempting driver crash "
                    "adoption within %.0fs", rec.get("epoch"),
                    len(want), budget)
        live: Dict[Slot, Tuple[str, int]] = {}
        while not self._shutdown.is_set():
            for slot in want:
                if slot in live:
                    continue
                addr = self._worker_addrs.get(slot) or addrs.get(slot)
                if addr is None:
                    continue
                try:
                    pong = send_message(addr, self._secret,
                                        {"kind": "ping"},
                                        timeout=2.0, retries=0)
                    if isinstance(pong, dict) and pong.get("ok"):
                        live[slot] = addr
                except Exception:  # noqa: BLE001 — probed again below
                    pass
            if len(live) == len(want) or time.monotonic() >= deadline:
                break
            time.sleep(0.2)
        if len(live) != len(want):
            missing = [s for s in want if s not in live]
            metrics.event("control_adopt_failed",
                          missing=len(missing), live=len(live))
            LOG.error(
                "driver crash adoption FAILED: %d/%d journaled workers "
                "unreachable within the %.0fs recovery deadline (%s); "
                "falling back to world re-formation (the elastic "
                "deadline governs from here)", len(missing), len(want),
                budget, ", ".join("%s:%d" % s for s in missing))
            for slot in missing:
                self._worker_addrs.purge(slot)
            return False
        with self._lock:
            self._epoch = int(rec["epoch"])
            self._target = target
            self._assignments = assignments
            self._port_base = int(rec.get("port_base") or 0)
            self._published = True
            self._ready = set(target)
            self._succeeded = set(succeeded)
            self._external = {s: 0 for s in want}
        metrics.gauge("elastic_epoch", **self._mlabels).set(self._epoch)
        metrics.event("control_adopted", epoch=self._epoch,
                      workers=len(live))
        LOG.warning("adopted epoch %d: all %d live workers reattached; "
                    "training continues WITHOUT a world re-formation",
                    self._epoch, len(live))
        self._journal_control()
        return True

    # Consecutive ping misses before an adopted worker is booked as
    # gone (one miss can be a GC pause or a busy accept queue).
    _EXTERNAL_PING_MISSES = 2

    def _check_external(self):
        """Liveness for adopted workers (no proc handle to poll):
        ping each external slot at a throttled cadence; sustained
        silence books the slot the way a reaped exit would — drained
        if it was told to stop/drain, a failure otherwise.  Returns
        (failed_hosts, drained_slots) for :meth:`_check_procs` to fold
        into its epilogue."""
        now = time.monotonic()
        if now - self._external_checked < 2.0:
            return [], []
        self._external_checked = now
        with self._lock:
            probes = [(s, self._worker_addrs.get(s))
                      for s in self._external]
        if not probes:
            return [], []
        results = {}
        for slot, addr in probes:
            ok = False
            if addr is not None:
                try:
                    pong = send_message(addr, self._secret,
                                        {"kind": "ping"},
                                        timeout=2.0, retries=0)
                    ok = bool(isinstance(pong, dict) and pong.get("ok"))
                except Exception:  # noqa: BLE001 — that IS the signal
                    ok = False
            results[slot] = ok
        failed_hosts, drained_slots = [], []
        with self._lock:
            for slot, ok in results.items():
                if slot not in self._external:
                    continue  # finished/re-booked while we pinged
                if ok:
                    self._external[slot] = 0
                    continue
                self._external[slot] += 1
                if self._external[slot] < self._EXTERNAL_PING_MISSES:
                    continue
                del self._external[slot]
                self._worker_addrs.purge(slot)
                if slot in self._draining or slot in self._stopped:
                    self._draining.discard(slot)
                    drained_slots.append(slot)
                    metrics.counter("elastic_drain_total",
                                    **self._mlabels).inc()
                    metrics.event("drained", host=slot[0],
                                  slot=slot[1], rc=-1, external=True)
                else:
                    failed_hosts.append(slot[0])
                    metrics.counter("elastic_worker_failures_total",
                                    **self._mlabels).inc()
                    metrics.event("worker_failed", host=slot[0],
                                  slot=slot[1], rc=-1, external=True)
                    LOG.warning("adopted worker %s:%d stopped "
                                "answering pings: booking a failure",
                                slot[0], slot[1])
        return failed_hosts, drained_slots

    # -- world management --------------------------------------------------

    def _recompute_world(self, reason: str):
        """Epoch bump: recompute target slots, spawn/stop workers,
        notify live ones (caller must NOT hold the lock)."""
        # Poll OUTSIDE the lock: platform proc proxies (Spark agents)
        # may do blocking RPCs, and the message handler needs the lock.
        with self._lock:
            snapshot = list(self._procs.items())
        polled = {slot: (mp, mp.poll() is None) for slot, mp in snapshot}
        with self._lock:
            def _alive(slot):
                if slot in self._external:
                    # Adopted worker: liveness is ping-based
                    # (_check_external); a slot still in the map is
                    # live as far as world math is concerned.
                    return True
                mp = self._procs.get(slot)
                if mp is None:
                    return False
                rec = polled.get(slot)
                if rec is not None and rec[0] is mp:
                    return rec[1]
                return True  # installed after the poll pass: fresh
            new_target = self._hosts.ordered_slots(self.max_np)
            if len(new_target) < self.min_np:
                if self._below_min_since is None:
                    self._below_min_since = time.monotonic()
                LOG.warning(
                    "world below min_np (%d < %d) after %s; waiting for "
                    "discovery", len(new_target), self.min_np, reason)
                new_target = []
            else:
                self._below_min_since = None
            if (new_target == self._target and self._published
                    and all(_alive(s) for s in new_target)
                    and self._rebuild_wanted <= self._epoch):
                return
            self._rebuild_wanted = 0
            self._epoch += 1
            self._target = new_target
            self._ready = set()
            self._published = False
            self._assignments = {}
            # A slot stopped in an earlier epoch that re-enters the
            # world must be spawnable again (stale membership would
            # block the reap-loop retry forever).
            self._stopped.difference_update(new_target)
            LOG.info("world change (%s): epoch %d, target %d slots",
                     reason, self._epoch, len(new_target))
            # Stop procs whose slot left the world (host removed, or a
            # shrunk host renumbered its slots away).
            for slot in list(self._procs):
                if slot not in new_target and _alive(slot):
                    self._stopped.add(slot)
            # An adopted worker whose slot left the world is told to
            # stop through rendezvous like anyone else; marking it
            # stopped books its eventual silence as a planned removal
            # (no blacklist) in _check_external.
            for slot in list(self._external):
                if slot not in new_target:
                    self._stopped.add(slot)
            # Collect target slots without a live process; the spawn
            # RPCs themselves run after the lock is released.  A slot
            # whose spawn is already in flight on the other thread is
            # skipped — double-spawning would race two real processes
            # for one rendezvous slot.
            to_spawn = [slot for slot in new_target
                        if not _alive(slot)
                        and slot not in self._pending_spawns]
            now = time.monotonic()
            for slot in to_spawn:
                self._pending_spawns.add(slot)
                self._spawn_attempts[slot] = now
        addrs = self._worker_addrs.items()
        self._journal_control()
        self._spawn_workers(to_spawn)
        # Notify outside the lock (network).
        for slot, addr in addrs:
            try:
                # One bounded retry: a worker mid-GC deserves a second
                # attempt, a dead one should not stall the recompute —
                # the reap loop owns dead-worker handling.  The deadline
                # must exceed one full socket timeout or the retry
                # could never actually run.
                send_message(addr, self._secret, {
                    "kind": "notify",
                    "payload": {"type": "hosts_updated",
                                "epoch": self._epoch}}, timeout=5.0,
                    retries=1, deadline=12.0)
            except Exception:  # noqa: BLE001 — worker may be dead
                pass
        # Terminate stopped procs off-lock too (AgentProc.terminate is
        # a network RPC); one shared grace window, not one per proc.
        with self._lock:
            to_stop = [mp for slot, mp in self._procs.items()
                       if slot in self._stopped]
        safe_shell_exec.terminate_all(
            [mp for mp in to_stop if mp.poll() is None])

    # -- pod-scheduler integration (elastic/scheduler.py) ------------------

    def scheduler_preempt(self, reason: str):
        """Scheduler-initiated preemption of this whole tenant world:
        a PLANNED removal riding the exact r10 drain path for every
        live slot — SIGTERM leads (``terminate_all``), the workers
        commit + spill and exit with the drain code inside the grace
        window, and NOTHING books as a failure: no blacklist entry, no
        ``HOROVOD_HOST_FAILURE_THRESHOLD`` count, respawn backoff
        reset, proactive epoch bump.  The driver then parks (held)
        with its below-min deadline suspended until
        :meth:`scheduler_resume`.

        Idempotent: the scheduler re-issues it every tick until the
        tenant's slot view is actually empty (the
        ``scheduler.preempt.notice`` drop injection loses one issue,
        the next tick repeats it)."""
        with self._lock:
            self._held = True
            self._below_min_since = None
            # Preemption is not a spawn failure: the next spawn of
            # these slots (at resume) starts from the base interval.
            self._spawn_backoff.clear()
            # Exits that race the recompute below must still book as
            # planned removals, whatever their rc.  Counting happens
            # at the reap (the ONE site incrementing
            # elastic_drain_total), not here.
            live = len(self._procs)
            for slot in self._procs:
                self._draining.add(slot)
        metrics.event("tenant_preempt_order", tenant=self.tenant_id,
                      reason=reason, live_slots=live)
        LOG.warning("scheduler preemption (%s): draining tenant %s's "
                    "world as a planned removal", reason, self.tenant_id)
        try:
            # The slot view the scheduler already emptied must reach
            # the HostManager before the recompute reads it.
            self._hosts.update_available_hosts()
        except Exception:  # noqa: BLE001 — view facade cannot really fail
            LOG.debug("preempt-time discovery refresh failed",
                      exc_info=True)
        self._recompute_world("scheduler preemption (%s)" % reason)

    def scheduler_resume(self):
        """Hand a preempted tenant its slots back: un-hold, refresh the
        slot view, and re-form the world — respawned workers restore
        from their r10 spill at the committed step during sync()."""
        with self._lock:
            self._held = False
            self._below_min_since = None
        metrics.event("tenant_resume_order", tenant=self.tenant_id)
        try:
            self._hosts.update_available_hosts()
        except Exception:  # noqa: BLE001 — view facade cannot really fail
            LOG.debug("resume-time discovery refresh failed",
                      exc_info=True)
        self._recompute_world("scheduler resume")

    def held(self) -> bool:
        with self._lock:
            return self._held

    def set_np_bounds(self, min_np: int, max_np: Optional[int]):
        """Adjust a LIVE driver's world-size bounds (the scheduler's
        ``resize`` propagation).  The driver snapshots ``min_np`` /
        ``max_np`` at construction and truncates every recomputed
        target to ``max_np`` — without this hook a scheduler resize
        would widen the tenant's slot view while the driver kept
        capping its world at the admission-time bound, and a serving
        scale-up could never converge.  Safe from any thread; triggers
        an immediate recompute (the widened view may already be
        visible) and the normal discovery poll re-derives after the
        next replan either way."""
        with self._lock:
            self.min_np = max(1, int(min_np))
            self.max_np = max_np
        self._recompute_world("np bounds resize")

    def live_worker_count(self) -> int:
        """Worker processes currently installed (spawned and not yet
        reaped).  The serving autoscaler's feedback signal: a resize
        order has ACTUALLY landed only when this converges on the new
        target — the gap between order and convergence is the
        cold-start window the serving SLO measures."""
        with self._lock:
            return len(self._procs)

    def target_world_size(self) -> int:
        """Slots in the current target world (0 while parked below
        min_np or held by the pod scheduler)."""
        with self._lock:
            return len(self._target)

    def request_stop(self):
        """Ask :meth:`run` to exit its reap loop and tear the world
        down (scheduler shutdown).  Thread-safe, idempotent."""
        self._shutdown.set()

    def _worker_env(self, slot: Slot) -> Dict[str, str]:
        host, idx = slot
        env = dict(self.env)
        env.update({
            "HOROVOD_ELASTIC": "1",
            "HOROVOD_ELASTIC_DRIVER_ADDR": "%s:%d" % (
                self._driver_host() or "127.0.0.1", self._server.port),
            "HOROVOD_ELASTIC_SLOT": str(idx),
            "HOROVOD_HOSTNAME": host,
            "HOROVOD_SECRET_KEY": self._secret,
            "HOROVOD_ELASTIC_TIMEOUT": str(self.elastic_timeout),
        })
        with self._lock:
            shares_host = any(h == host and i != idx
                              for h, i in self._target)
        if shares_host:
            # The slot index is the one per-host number that survives
            # every epoch, so it names the worker's chip.
            from ..runner.launch import own_chip_env
            env.update(own_chip_env(idx))
        if self.tenant_id is not None:
            # Tenant identity travels with the worker: KV namespace,
            # spill subdirectory and @tenant= fault targeting all key
            # off it (docs/elastic.md §Multi-tenant scheduling).
            env["HOROVOD_TENANT_ID"] = str(self.tenant_id)
            if self.tenant_priority is not None:
                env["HOROVOD_TENANT_PRIORITY"] = str(self.tenant_priority)
        return env

    def _make_worker_proc(self, slot: Slot, env: Dict[str, str]):
        """Start one worker process for ``slot``; returns a proc-like
        object with ``poll()``/``terminate()``.  Platform integrations
        (Spark task agents) override this to place workers themselves."""
        host, idx = slot
        is_local = (host == "localhost" or host.startswith("127.")
                    or host == util.host_hash())
        if is_local:
            cmd = self.command
        else:
            from ..runner.launch import _ssh_wrap
            cmd = _ssh_wrap(host, self.ssh_port, env, self.command)
        prefix = "[%s:%d]" % (host, idx)
        return safe_shell_exec.ManagedProcess(
            cmd, env,
            stdout_sink=lambda l, p=prefix: sys.stdout.write(
                p + "<stdout>" + l),
            stderr_sink=lambda l, p=prefix: sys.stderr.write(
                p + "<stderr>" + l))

    def _spawn_workers(self, slots):
        """Start workers for ``slots``, doing the spawn itself OUTSIDE
        the lock — platform carriers (Spark agents) may block on a
        network RPC and the message handler needs the lock — then
        install the returned procs under the lock.  Every slot here is
        in ``self._pending_spawns`` (set by the caller under the lock),
        which keeps the reap loop and the discovery thread from double-
        spawning the same slot while the RPC is in flight.

        A spawn that raced a world change (slot dropped from the
        target) or the shutdown is terminated instead of installed;
        the worker's env is epoch-independent, so a spawn that merely
        crossed an epoch bump while its slot stayed in the target is
        still the process the new epoch wants."""
        for slot in slots:
            host, idx = slot
            try:
                if faultline.site("driver.spawn.attempt"):
                    # Injected declined spawn: same shape as a carrier
                    # refusing the slot — the reap loop retries with
                    # exponential backoff.
                    LOG.warning("spawn attempt for %s:%d dropped "
                                "(faultline driver.spawn.attempt)",
                                host, idx)
                    mp = None
                else:
                    mp = self._make_worker_proc(
                        slot, self._worker_env(slot))
            finally:
                # Cleared before install so a failure can't wedge the
                # slot; install below re-checks under the same lock.
                with self._lock:
                    self._pending_spawns.discard(slot)
            if mp is None:
                # Platform overrides may decline (agent not registered
                # yet); the next recompute retries.
                LOG.info("no carrier for worker %s:%d yet", host, idx)
                continue
            with self._lock:
                stale = (self._shutdown.is_set()
                         or slot not in self._target
                         or slot in self._stopped)
                if not stale:
                    self._procs[slot] = mp
                    self._succeeded.discard(slot)
                    # A fresh process is not draining, whatever its
                    # predecessor announced (a late drain notice must
                    # not relabel this incarnation's future failures).
                    self._draining.discard(slot)
                    # A successful spawn resets the slot's respawn
                    # backoff to the base interval.
                    self._spawn_backoff.pop(slot, None)
            if not stale:
                metrics.counter("elastic_spawn_total", **self._mlabels).inc()
                metrics.event("spawn", host=host, slot=idx)
            if stale:
                # The pending guard means no replacement proc can exist
                # for this slot, so terminating the carrier (for agent
                # proxies: the agent's single proc slot) only ever kills
                # the process this very call started.
                try:
                    mp.terminate()
                except Exception:  # noqa: BLE001 — best-effort cleanup
                    pass
            else:
                LOG.info("spawned worker %s:%d", host, idx)

    # -- monitoring --------------------------------------------------------

    def _discovery_tick(self):
        """One discovery pass with flake tolerance: a transient failure
        keeps the last good host view; a streak reaching
        ``discovery_failure_threshold`` escalates by invalidating the
        view — the world goes below ``min_np`` and the existing elastic
        deadline fails the run LOUDLY unless discovery recovers first
        (a later successful pass re-adds the hosts and the world
        re-forms)."""
        result = None
        try:
            result = self._hosts.update_available_hosts()
        except Exception as exc:  # noqa: BLE001 — counted, bounded
            self._discovery_failures += 1
            if self._discovery_failures < self.discovery_failure_threshold:
                LOG.warning(
                    "host discovery failed (%d/%d consecutive; keeping "
                    "last good host view): %s",
                    self._discovery_failures,
                    self.discovery_failure_threshold, exc)
            elif self._discovery_failures == \
                    self.discovery_failure_threshold:
                LOG.error(
                    "host discovery failed %d consecutive times: %s — "
                    "escalating: the host view is no longer trusted; "
                    "the run fails via the elastic deadline (%.0fs) "
                    "unless discovery recovers",
                    self._discovery_failures, exc, self.elastic_timeout)
                self._hosts.invalidate()
                self._recompute_world("discovery escalation")
                return
            else:
                LOG.warning(
                    "host discovery still failing (%d consecutive): %s",
                    self._discovery_failures, exc)
        if result is not None and self._discovery_failures:
            LOG.info("host discovery recovered after %d failure(s)",
                     self._discovery_failures)
            self._discovery_failures = 0
        if result is not None and result != HostUpdateResult.NO_UPDATE:
            self._recompute_world("discovery update")
        elif self._rebuild_wanted > self._epoch:
            # Racy read (no lock): a just-raised demand is caught on
            # the next tick at the latest.  Checked on FAILED ticks
            # too: a worker-reported broken world (min_epoch demand is
            # its only signal) must not wait out a discovery flake
            # streak before being serviced.
            self._recompute_world("worker-reported broken world")

    def _discovery_loop(self):
        while not self._shutdown.is_set():
            self._discovery_tick()
            self._shutdown.wait(self.discovery_interval)

    def _check_procs(self) -> bool:
        """Reap exited workers; returns True when the run is finished."""
        # Adopted (externally-spawned) workers first: their ping-based
        # liveness feeds the same failure/drain epilogue as the reap.
        failed_hosts, drained_slots = self._check_external()
        # Poll OUTSIDE the lock: platform proc proxies (Spark agents)
        # may do blocking RPCs, and the message handler needs the lock.
        with self._lock:
            snapshot = list(self._procs.items())
        polled = [(slot, mp, mp.poll()) for slot, mp in snapshot]
        reaped = False
        with self._lock:
            for slot, mp, rc in polled:
                if rc is None or self._procs.get(slot) is not mp:
                    continue  # alive, or replaced while we polled
                del self._procs[slot]
                reaped = True
                if slot in self._stopped:
                    # A stopped slot that was ALSO marked draining (a
                    # scheduler preemption) still counts as a drain —
                    # exactly once, here at the reap — while keeping
                    # the stopped slot's exemption from every other
                    # bookkeeping branch.
                    if slot in self._draining:
                        self._draining.discard(slot)
                        metrics.counter("elastic_drain_total",
                                        **self._mlabels).inc()
                        metrics.event("drained", host=slot[0],
                                      slot=slot[1], rc=rc)
                    continue
                drained = (slot in self._draining
                           or rc == DRAIN_EXIT_CODE)
                if drained:
                    # Planned removal (preemption drain, stall abort):
                    # extend the r8 clean-exit rule — no blacklist, no
                    # failure count, respawn backoff reset to base.
                    # The rc fallback covers a drain notice (or its
                    # ack) lost in flight.  NOT a success either: the
                    # slot's work is unfinished and it respawns if its
                    # host stays discovered.
                    self._draining.discard(slot)
                    self._spawn_backoff.pop(slot, None)
                    self._registry.record_success(slot[0])
                    drained_slots.append(slot)
                    metrics.counter("elastic_drain_total",
                                    **self._mlabels).inc()
                    metrics.event("drained", host=slot[0], slot=slot[1],
                                  rc=rc)
                    LOG.warning("worker %s:%d drained (rc=%d): planned "
                                "removal, host not blacklisted",
                                slot[0], slot[1], rc)
                elif rc == 0:
                    self._succeeded.add(slot)
                    self._registry.record_success(slot[0])
                    # A clean exit resets the slot's respawn throttle
                    # too: the next spawn on this slot (a later epoch)
                    # starts from the base interval.
                    self._spawn_backoff.pop(slot, None)
                else:
                    metrics.counter("elastic_worker_failures_total",
                                    **self._mlabels).inc()
                    metrics.event("worker_failed", host=slot[0],
                                  slot=slot[1], rc=rc)
                    LOG.warning("worker %s:%d failed (rc=%d)",
                                slot[0], slot[1], rc)
                    failed_hosts.append(slot[0])
            # Retry target slots with no process: a platform carrier may
            # have declined the spawn (agent busy / not yet registered);
            # without this the run would wait forever on a slot nothing
            # is driving.  Throttled per slot with exponential backoff —
            # each attempt can be a network RPC, and a slot that keeps
            # failing to start should lean on its host progressively
            # less (the backoff resets when a spawn succeeds).
            now = time.monotonic()
            to_spawn = []
            for slot in self._target:
                wait = self._spawn_backoff.get(
                    slot, self.respawn_backoff_base)
                # A slot that drained THIS pass must wait out the epoch
                # bump below (the failure path already does, via the
                # failed_hosts exclusion): a same-pass respawn can
                # rendezvous into the still-PUBLISHED stale epoch,
                # resolve the old world's coordinator, and its
                # new-incarnation connect FATALs the surviving members
                # mid-recovery (seen live under the straggler-drain
                # e2e).  The next reap pass respawns it into the
                # re-formed world.
                if slot not in self._procs and slot not in self._stopped \
                        and slot not in self._succeeded \
                        and slot not in self._pending_spawns \
                        and slot not in self._external \
                        and slot[0] not in failed_hosts \
                        and slot not in drained_slots \
                        and now - self._spawn_attempts.get(slot, 0) >= wait:
                    self._spawn_attempts[slot] = now
                    self._spawn_backoff[slot] = min(
                        max(wait, self.respawn_backoff_base) * 2,
                        self.respawn_backoff_cap)
                    self._pending_spawns.add(slot)
                    to_spawn.append(slot)
            target = list(self._target)
            done = (bool(target) and self._published
                    and all(s in self._succeeded for s in target))
        self._spawn_workers(to_spawn)
        if reaped:
            # Success/failure bookkeeping changed: the journaled
            # control record must follow (world changes journal inside
            # _recompute_world below).
            self._journal_control()
        if done:
            self._rc = 0
            return True
        for host in set(failed_hosts):
            if self._registry.record_failure(host):
                cooldown = self._registry.cooldown_for(host)
                metrics.counter("elastic_blacklist_total",
                                **self._mlabels).inc()
                metrics.event("blacklist", host=host,
                              cooldown_secs=cooldown)
                LOG.warning(
                    "blacklisting host %s (%s)", host,
                    "cooldown %.1fs, then eligible to rejoin" % cooldown
                    if cooldown else "permanently: no cooldown configured")
        if failed_hosts:
            self._hosts.blacklist_refresh()
            self._recompute_world("worker failure")
        elif drained_slots:
            # A drained slot changes the live world without a failure:
            # bump the epoch proactively so survivors re-rendezvous at
            # their next commit (HostsUpdatedInterrupt, no rollback)
            # instead of discovering the hole via a failed collective.
            self._recompute_world("worker drained")
        with self._lock:
            # A held driver (scheduler preemption) parks below min_np
            # BY DESIGN: the deadline belongs to worlds that cannot
            # re-form, not to tenants whose slots the pod scheduler is
            # deliberately holding.
            if (not self._held
                    and self._below_min_since is not None
                    and time.monotonic() - self._below_min_since
                    > self.elastic_timeout):
                LOG.error("gave up: below min_np for %.0fs",
                          self.elastic_timeout)
                self._rc = 1
                return True
        return False

    # -- entry -------------------------------------------------------------

    def _pull_worker_snapshots(self):
        """Every live worker's metrics snapshot over the notification
        service: ``[(rank_label, slot, model)]``.  A dead or
        mid-respawn worker is skipped — neither the /metrics scrape
        nor the skew tick may block on the control plane's health."""
        with self._lock:
            live = set(self._procs) | set(self._external)
        addrs = self._worker_addrs.items()

        def pull(slot, addr):
            try:
                return slot, send_message(addr, self._secret,
                                          {"kind": "metrics"},
                                          timeout=2.0, retries=0)
            except Exception:  # noqa: BLE001 — worker may be gone
                return slot, None

        # Concurrent pulls: dead/mid-respawn workers each cost a full
        # connect timeout, and a sequential loop would stack them —
        # the scrape would exceed Prometheus' own timeout exactly
        # during the failure event it exists to observe.
        from concurrent.futures import ThreadPoolExecutor
        addrs = [(s, a) for s, a in addrs if not live or s in live]
        if addrs:
            with ThreadPoolExecutor(
                    max_workers=min(len(addrs), 16)) as pool:
                results = list(pool.map(lambda sa: pull(*sa), addrs))
        else:
            results = []
        models = []
        for slot, resp in results:
            if not isinstance(resp, dict) or not resp.get("snapshot"):
                continue
            rank = resp.get("rank")
            label = str(rank) if rank is not None \
                else "%s:%d" % (slot[0], slot[1])
            models.append((label, slot, resp["snapshot"]))
        return models

    def _metrics_text(self) -> str:
        """Fleet-wide Prometheus scrape: this driver's registry merged
        with every registered worker's snapshot."""
        models = [("driver", metrics.snapshot())]
        models.extend((label, model) for label, _slot, model
                      in self._pull_worker_snapshots())
        return metrics.render_merged(models)

    # -- skew observatory (straggler detection / plan staleness) -----------

    def _skew_text(self) -> str:
        """``GET /skew``: the observatory's latest fleet view as JSON
        (the skew loop keeps it fresh; the handler never pulls — a
        scrape must not trigger actuation or block on workers)."""
        import json
        return json.dumps(self._observatory.describe(), default=str)

    def _skew_tick(self):
        """One observe pass: pull worker snapshots, feed the
        observatory (scores + sustained-detection + the configured
        action + plan-staleness tracking + the data-plane resilience
        roll-up)."""
        models = self._pull_worker_snapshots()
        if not models:
            return
        self._observatory.observe(models)
        # Operator visibility for the self-healing data plane: a route
        # demotion is a fleet-level bandwidth event (hier -> flat), so
        # the driver logs each CHANGE of the demoted set loudly — the
        # steady state stays quiet, /skew carries the live view.
        res = getattr(self._observatory, "_resilience", None) or {}
        demoted = tuple(sorted(
            (d["op"], d["size_class"])
            for d in res.get("degraded_routes", ())))
        if demoted != getattr(self, "_degraded_seen", ()):
            if demoted:
                LOG.warning(
                    "fleet reports degraded collective routes "
                    "(hier -> flat): %s; failures by reason: %s",
                    ["%s/%s" % d for d in demoted],
                    res.get("failures_by_reason", {}))
            elif getattr(self, "_degraded_seen", ()):
                LOG.warning(
                    "fleet degraded collective routes cleared "
                    "(re-promotion probe succeeded)")
            self._degraded_seen = demoted

    def _skew_loop(self):
        # Cadence: a few samples per detection window, bounded so a
        # tiny test window cannot spin the control plane and a huge
        # one still refreshes /skew.
        cadence = min(max(self._observatory.window_secs / 4.0, 0.5), 5.0)
        while not self._shutdown.is_set():
            self._shutdown.wait(cadence)
            if self._shutdown.is_set():
                return
            try:
                self._skew_tick()
            except Exception:  # noqa: BLE001 — observing must not kill
                LOG.exception("skew tick failed; retrying next tick")

    def _straggler_drain(self, slot) -> bool:
        """Actuate a straggler detection through the r10 planned-
        removal path: mark the slot draining, then SIGTERM it — the
        worker finishes its in-flight step, commits (+spills) and
        exits with the drain code inside the grace window; the reap
        books a drain (no blacklist, no failure count) and the epoch
        bump re-forms the world without the straggler.  Its host stays
        discovered, so a FRESH process respawns into the next epoch —
        mitigation removes the wedged incarnation, not the capacity."""
        if not isinstance(slot, tuple):
            return False
        with self._lock:
            mp = self._procs.get(slot)
            if mp is None or slot in self._draining \
                    or slot in self._stopped:
                return False
            self._draining.add(slot)
            # A straggler drain is not a spawn failure: the slot's
            # next spawn starts from the base interval.
            self._spawn_backoff.pop(slot, None)
        metrics.event("straggler_drain_order", host=slot[0],
                      slot=slot[1], tenant=self.tenant_id)
        LOG.warning("draining straggler %s:%d (planned removal — the "
                    "world re-forms without it before it stalls a "
                    "collective)", slot[0], slot[1])
        # Off-lock: terminate waits out the shared grace window.
        if mp.poll() is None:
            safe_shell_exec.terminate_all([mp])
        return True

    def _straggler_shrink(self, slot) -> bool:
        """Actuate via the pod scheduler: shrink this tenant's share
        by one slot (resize + poke, wired by
        ``PodScheduler._make_driver``), naming the straggler's HOST so
        the packer sheds from it rather than from an arbitrary healthy
        slot.  Standalone drivers have no scheduler to shrink through
        — the observatory warns and keeps observing."""
        if self.scheduler_shrink is None:
            return False
        host, idx = slot if isinstance(slot, tuple) else (None, -1)
        metrics.event("straggler_shrink_order", tenant=self.tenant_id,
                      host=host, slot=idx)
        return bool(self.scheduler_shrink(host=host))

    def run(self) -> int:
        if self.tenant_id is None:
            # A tenant driver runs INSIDE the scheduler process, whose
            # journal tag ("scheduler") covers the whole process — a
            # per-tenant override here would misattribute every other
            # thread's events written after this point.
            metrics.set_journal_tag("driver")
        self._server.start()
        self._kv.start()
        try:
            # Crash adoption first: if a journaled control record's
            # workers all reattach, the old world continues at its
            # published epoch and the startup rendezvous is skipped
            # (discovery still seeds its view below for elasticity).
            adopted = (self._adopt_rec is not None
                       and self._try_adopt())
            if adopted:
                try:
                    self._hosts.update_available_hosts()
                except Exception as exc:  # noqa: BLE001 — flaky script
                    LOG.warning("post-adoption discovery failed: %s",
                                exc)
            else:
                deadline = time.monotonic() + self.start_timeout
                while True:
                    try:
                        self._hosts.update_available_hosts()
                    except Exception as exc:  # noqa: BLE001
                        LOG.warning("startup discovery failed: %s", exc)
                    with self._lock:
                        lo, hi = self.min_np, self.max_np
                    if len(self._hosts.ordered_slots(hi)) >= lo:
                        break
                    if self._shutdown.is_set():
                        return self._rc
                    if time.monotonic() > deadline and not self.held():
                        LOG.error("discovery never found min_np=%d "
                                  "hosts", lo)
                        return 1
                    time.sleep(1.0)
                self._recompute_world("startup")
            disc = threading.Thread(target=self._discovery_loop,
                                    daemon=True)
            disc.start()
            # The observatory's pull loop: always on (scores + /skew
            # stay live even with detection disabled); detection and
            # actuation are governed by the HOROVOD_STRAGGLER_* knobs.
            threading.Thread(target=self._skew_loop, daemon=True,
                             name="skew-observatory").start()
            # The shutdown event doubles as the scheduler's stop
            # request (request_stop): a managed tenant driver must be
            # stoppable without its world ever reaching "done".
            while not self._shutdown.is_set() and not self._check_procs():
                time.sleep(0.1)
            return self._rc
        finally:
            self._shutdown.set()
            with self._lock:
                procs = list(self._procs.values())
            # One shared grace window for the whole world: serial
            # per-proc terminates would multiply the drain grace by
            # the straggler count.
            safe_shell_exec.terminate_all(procs)
            self._server.stop()
            self._kv.stop()


def elastic_run(args, base_env=None) -> int:
    """Entry from the launcher (``horovodrun --min-np ... --host-
    discovery-script disc.sh python train.py``).  ``base_env`` overlays
    the workers' base environment (the programmatic ``run`` path)."""
    from ..runner.launch import build_common_env
    if getattr(args, "tpu_discovery", False):
        from .discovery import TpuSliceDiscovery
        discovery = TpuSliceDiscovery(
            slots_per_host=getattr(args, "tpu_discovery_slots", 1))
    elif args.host_discovery_script:
        discovery = HostDiscoveryScript(args.host_discovery_script)
    else:
        hosts = util.parse_hosts(args.hosts) if args.hosts else \
            [util.HostInfo("127.0.0.1", args.np or 1)]
        discovery = FixedHosts({h.hostname: h.slots for h in hosts})
    min_np = args.min_np or args.np or 1
    max_np = args.max_np
    driver = ElasticDriver(
        args.command, discovery, min_np, max_np,
        env=build_common_env(args, base_env),
        elastic_timeout=args.elastic_timeout,
        ssh_port=getattr(args, "ssh_port", 22))
    return driver.run()
