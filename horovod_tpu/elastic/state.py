"""Elastic state: commit / restore / sync across world changes.

Reference parity: ``horovod/common/elastic.py`` (``State``,
``ObjectState``, ``run_fn``) and ``horovod/torch/elastic/state.py``
(``TorchState`` — here ``JaxState`` holding pytrees).  The contract:

* ``commit()``  — snapshot state in host memory AND check for pending
  host updates (cheap in-memory checkpoint; called every N batches).
  With ``HOROVOD_STATE_SPILL_DIR`` / ``HOROVOD_STATE_REPLICAS`` set
  the snapshot is additionally spilled to disk and/or mirrored to
  buddy ranks (elastic/spill.py), so full-job restart and multi-host
  loss restore from the newest valid blob.
* ``restore()`` — roll back to the last commit (after a failure).
* ``sync()``    — broadcast state to the (possibly new) world after a
  re-rendezvous, from a **survivor-elected root**: every rank
  allgathers a small commit-metadata record, the max-progress rank
  wins deterministically on all ranks, and a blank joiner can never
  overwrite survivors' progress (the reference broadcasts from rank 0
  and assumes survivors keep low ranks; our driver makes no such
  guarantee).
* user code runs inside ``hvd.elastic.run(train)(state)`` which retries
  on ``HorovodInternalError`` (restore) and ``HostsUpdatedInterrupt``
  (no rollback), re-rendezvousing in between; a SIGTERM/preemption
  notice (or a stall crossing the shutdown threshold) leaves through
  the drain protocol instead — commit, notify the driver, exit with
  the distinguished drain code.
"""

from __future__ import annotations

import copy
import functools
import logging
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..common import basics, faultline, metrics
from ..ops.engine import HorovodInternalError
from ..utils.stall_inspector import StallError
from . import shardspill, spill
from .worker import (HostsUpdatedInterrupt, WorkerDrained, WorkerStopped,
                     arm_last_resort_exit, elastic_timeout,
                     install_assignment, install_preemption_handler,
                     notification_manager, preempt_grace_secs)

LOG = logging.getLogger("horovod_tpu.elastic")


class StateSyncError(RuntimeError):
    """``sync()`` refused to proceed: the elected root holds no
    committed state while durable evidence says state existed, or the
    broadcast would regress this rank's progress.  Loud by design —
    the alternative is silently training from reinitialized zeros."""


class State:
    """Base elastic state (reference horovod/common/elastic.py State)."""

    def __init__(self, **kwargs):
        self._reset_callbacks: List[Callable[[], None]] = []
        # Monotonic commit counter: 0 = never committed.  Drives the
        # sync()-time root election (max progress wins) and names the
        # durable spill blobs; a synced rank adopts the root's id.
        self._commit_id = 0
        self._sync_root: Optional[int] = None
        for k, v in kwargs.items():
            setattr(self, k, v)

    def register_reset_callbacks(self, callbacks):
        self._reset_callbacks.extend(callbacks)

    def on_reset(self):
        for cb in self._reset_callbacks:
            cb()

    def commit(self):
        faultline.site("elastic.state.commit")
        # Tenant-targeted kill seam: multi-tenant isolation tests arm
        # die/wedge here with @tenant=<id> so exactly one tenant's
        # workers go down while every tenant runs identical user code.
        faultline.site("tenant.worker.die")
        self._commit_id += 1
        self.save()
        self._persist()
        # Opt-in SPMD degraded-route check (HOROVOD_DATA_PLANE_CHECK_
        # EVERY commits): commits are the natural synchronized point —
        # every member reaches the same commit count, so the rank-0
        # route verdict is adopted at the same index everywhere.
        from ..common import resilience
        resilience.maybe_check_at_commit()
        self.check_drain()
        self.check_host_updates()

    def check_drain(self):
        """Leave via the drain protocol when a preemption notice
        arrived: the step just finished and the state is committed (and
        persisted), so this is the one safe exit point.  Checked before
        host updates — a preempted worker re-rendezvousing would waste
        its whole grace window."""
        nm = notification_manager()
        if faultline.site("worker.preempt.sigterm"):
            nm.request_drain(
                "injected preemption (faultline worker.preempt.sigterm)")
        if nm.drain_requested():
            # WARNING on purpose: preemption is the operator-visible
            # event the drain e2e tests (and humans) key on.
            LOG.warning("draining at commit %d: in-flight step "
                        "finished and committed; notifying the driver "
                        "and exiting", self._commit_id)
            nm.send_drain_notice(commit_id=self._commit_id)
            # Commit + notice are safe: shrink the force-exit window to
            # a teardown allowance, so a shutdown wedged on the broken
            # collective cannot eat the rest of the preemption grace.
            nm.arm_drain_exit(min(5.0, preempt_grace_secs()))
            raise WorkerDrained()

    def check_host_updates(self):
        """Raise HostsUpdatedInterrupt if the driver notified a member
        of a world change since the last check.  Every member leaves at
        the same commit (the reference's rule): the driver's notices
        reach the workers one after another, and a member that left a
        commit before its peer waits in the old world's shutdown while
        the peer waits for it in the next step's collective."""
        nm = notification_manager()
        updated = nm.has_update()
        if nm.active and basics.is_initialized() and basics.size() > 1:
            from ..ops.api import MAX, allreduce
            updated = bool(np.asarray(allreduce(
                np.asarray([updated], np.int32), op=MAX,
                name="elastic.hosts_updated")).reshape(-1)[0])
        if updated:
            nm.consume_update()
            raise HostsUpdatedInterrupt(skip_sync=False)

    # Subclass hooks -------------------------------------------------------
    def save(self):
        raise NotImplementedError

    def restore(self):
        raise NotImplementedError

    def sync(self):
        raise NotImplementedError

    def _persist(self):
        """Durable-commit hook (spill + buddy replication); base state
        has no serializable payload."""


class ObjectState(State):
    """Attribute-bag state synced by pickling (reference ObjectState):
    every public attribute is committed/restored/broadcast."""

    def __init__(self, **kwargs):
        self._saved: Dict[str, Any] = {}
        super().__init__(**kwargs)
        self.save()

    def _public_attrs(self) -> Dict[str, Any]:
        return {k: v for k, v in self.__dict__.items()
                if not k.startswith("_")}

    def save(self):
        self._saved = copy.deepcopy(self._public_attrs())

    def restore(self):
        for k, v in copy.deepcopy(self._saved).items():
            setattr(self, k, v)

    # -- durability (spill + buddy replication) ----------------------------

    def _spill_payload(self) -> Dict[str, Any]:
        return {"attrs": self._saved}

    def _load_payload(self, payload: Dict[str, Any]):
        self._saved = payload.get("attrs", {})
        self.restore()

    def _sharded_world(self) -> bool:
        """Sharded spill engages only where it helps: a real
        multi-process world (each member writes its 1/K byte range to
        the SHARED directory).  In-process and single-rank worlds keep
        the whole-blob path — there is no second writer to shard
        across."""
        return (shardspill.enabled()
                and basics.is_initialized() and basics.size() > 1
                and not basics._controller_is_spmd())

    def _persist(self):
        if spill.spill_dir() is None and spill.replica_count() <= 0:
            return
        if self._sharded_world() and spill.spill_dir() is not None:
            buf, layout = shardspill.flatten_state(self._spill_payload())
            shardspill.write_commit(
                self._commit_id, buf, layout,
                shard_index=basics.rank(), n_shards=basics.size(),
                tag="r%d" % basics.rank())
            # Shard buddy copies replace the whole-blob buddy
            # mirroring: one commit's bytes land ~(1+replicas)/K per
            # writer instead of whole-state per writer.
            return
        payload = pickle.dumps(self._spill_payload())
        tag = "r%d" % (basics.rank() if basics.is_initialized() else 0)
        spill.write(self._commit_id, payload, tag)
        replicas = spill.replica_count()
        if replicas > 0:
            notification_manager().mirror_commit(
                spill.encode(self._commit_id, payload),
                self._commit_id, replicas)

    def _durable_evidence(self) -> bool:
        return (spill.have_evidence()
                or shardspill.have_evidence()
                or notification_manager().replica_blob() is not None)

    def _adopt_durable_state(self) -> bool:
        """Load the newest valid durable blob (local spill or a buddy
        replica) when it is strictly newer than memory — the full-job
        restart and multi-host loss recovery path.  Mid-job syncs are
        no-ops here: memory is always at least as new as the disk."""
        best: Optional[tuple] = None  # (commit_id, payload, source)
        loaded = spill.load_newest(min_commit_id=self._commit_id)
        if loaded is not None:
            best = (loaded[0], loaded[1], "spill")
        rep = notification_manager().replica_blob()
        if rep is not None and rep.get("blob"):
            try:
                rid, rpayload = spill.decode(rep["blob"])
                if rid > self._commit_id and (best is None
                                              or rid > best[0]):
                    best = (rid, rpayload,
                            "replica of rank %s" % rep.get("source_rank"))
            except spill.SpillCorrupt as exc:
                metrics.counter("spill_crc_failures_total").inc()
                metrics.event("spill_corrupt",
                              source="replica of rank %s"
                                     % rep.get("source_rank"),
                              error=str(exc))
                LOG.warning("buddy replica blob is corrupt (%s); "
                            "ignoring it", exc)
        # Sharded commits, local path: when the collective streaming
        # path will not run (fresh single process, the N→1 resize,
        # in-process worlds — or HOROVOD_STATE_SHARD_SPILL rolled back
        # while sharded files remain), the newest fully-readable
        # sharded commit competes as a whole.  Gated on the FILES, not
        # the env flag: sharded blobs count as durable evidence
        # whatever the flag says, so restore must be reachable for
        # them too — otherwise a flag rollback turns valid commits
        # into a permanently refused restart.
        if shardspill.have_evidence() and not self._sharded_world():
            floor = max(self._commit_id,
                        best[0] if best is not None else 0)
            loaded = shardspill.restore_local(min_commit=floor)
            if loaded is not None:
                self._load_payload(loaded[1])
                self._commit_id = loaded[0]
                self.save()
                LOG.info("restored sharded durable state at commit %d "
                         "(local whole-state read)", self._commit_id)
                return True
        if best is None:
            return False
        self._load_payload(pickle.loads(best[1]))
        self._commit_id = best[0]
        self.save()
        LOG.info("restored durable state at commit %d from %s",
                 self._commit_id, best[2])
        return True

    def _adopt_sharded_collective(self) -> bool:
        """N→M resharding restore: the reader world agrees on the
        newest commit EVERY member can stream its own 1/M byte range
        for (per-shard buddy fallback inside a commit, per-commit
        fallback down the chain), then assembles the full state over
        the collective plane — no member reads more than its ranges
        (plus CRC-validation slop) from durable storage.  Symmetric:
        every rank makes the same calls, so it is collectively safe
        inside sync()."""
        if not self._sharded_world():
            return False
        from ..jax.functions import allgather_object
        n, r = basics.size(), basics.rank()
        # min_commit = own commit: nothing at or below ANY member's
        # commit can win (the c > max_commit gate below), so mid-job
        # syncs skip the manifest parsing entirely instead of
        # re-reading up to keep-K full layout descriptors per
        # re-rendezvous.
        cands = shardspill.restore_candidates(
            min_commit=self._commit_id) \
            if spill.spill_dir() is not None else []
        recs = allgather_object(
            {"rank": r, "commit": self._commit_id, "cands": cands},
            name="elastic.shardspill.plan")
        max_commit = max(int(x.get("commit", 0)) for x in recs)
        shared = set(recs[0].get("cands", []))
        for x in recs[1:]:
            shared &= set(x.get("cands", []))
        # Adopt only past EVERY member's in-memory progress: if any
        # survivor is at/val beyond the disk commit, its memory state
        # wins the election instead (disk is never newer than a live
        # member's memory within one job incarnation).
        for cid in sorted((c for c in shared if c > max_commit),
                          reverse=True):
            manifest = shardspill.load_manifest(cid)
            ok, mine = manifest is not None, {}
            if ok:
                n_src = int(manifest["n_shards"])
                # Round-robin whole-shard ownership: reader j streams
                # source shards s % M == j — ≤ ⌈N/M⌉ shards per host,
                # strictly under full-state size for M ≥ 2 (whole
                # shards, so each read CRC-validates exactly what it
                # streams, no overlap slop).
                try:
                    mine = shardspill.read_shards(
                        manifest, [s for s in range(n_src)
                                   if s % n == r])
                except shardspill.ShardUnavailable as exc:
                    LOG.warning(
                        "sharded commit %d not streamable on rank %d "
                        "(%s); world falls back to the previous "
                        "commit", cid, r, exc)
                    ok = False
            gathered = allgather_object(
                {"rank": r, "ok": ok, "shards": mine},
                name="elastic.shardspill.range")
            if not all(g.get("ok") for g in gathered):
                continue
            merged: dict = {}
            for g in gathered:
                merged.update(g.get("shards") or {})
            n_src = int(manifest["n_shards"])
            if set(merged) != set(range(n_src)):
                LOG.warning("sharded commit %d reassembly is missing "
                            "shards %s; falling back", cid,
                            sorted(set(range(n_src)) - set(merged)))
                continue
            buf = b"".join(merged[s] for s in range(n_src))
            self._load_payload(shardspill.unflatten_state(
                buf, manifest["layout"]))
            self._commit_id = cid
            self.save()
            LOG.info("restored sharded durable state at commit %d "
                     "(N=%d writers -> M=%d readers; this rank "
                     "streamed %d source shard(s))", cid, n_src, n,
                     len(mine))
            return True
        return False

    # -- sync with survivor-elected root -----------------------------------

    def _elect_sync_root(self) -> int:
        """Allgather commit metadata, elect the max-progress rank as
        root — identically on every rank — and refuse the blank-root
        hazard loudly (a freshly-joined rank must never broadcast its
        reinitialized state over survivors' progress)."""
        from ..jax.functions import elect_state_root
        record = {"rank": basics.rank(),
                  "commit_id": self._commit_id,
                  "evidence": self._durable_evidence(),
                  # The newest sharded-commit manifest this rank can
                  # see: election evidence carries the manifest, so a
                  # refused blank restart can name the durable commit
                  # it refused over (and operators can see which rank
                  # sees which durable history).
                  "manifest_commit": shardspill.newest_manifest_commit()
                  if shardspill.enabled() else 0}
        root, records = elect_state_root(record)
        root_commit = int(root.get("commit_id", 0))
        if any(int(r.get("commit_id", 0)) > root_commit
               for r in records):
            raise StateSyncError(
                "state-root election violated its own invariant: "
                "elected rank %r at commit %d but a rank reports more "
                "progress (records: %r)" % (root.get("rank"),
                                            root_commit, records))
        if root_commit == 0 and any(r.get("evidence") for r in records):
            raise StateSyncError(
                "no rank holds committed state but durable commit "
                "evidence exists (spill/replica blobs); refusing to "
                "silently restart from reinitialized state — "
                "inspect HOROVOD_STATE_SPILL_DIR")
        metrics.counter("elastic_elections_total").inc()
        metrics.event("election", root_rank=int(root.get("rank", -1)),
                      root_commit=root_commit,
                      my_commit=self._commit_id)
        if root_commit > 0:
            LOG.info("elastic sync: elected rank %d as state root "
                     "(commit id %d)", int(root["rank"]), root_commit)
        return int(root["rank"])

    def sync(self):
        self._sync_root = None
        adopted = self._adopt_durable_state()
        # Sharded commits in a live multi-rank world stream N→M over
        # the collective plane (symmetric on every rank) — this must
        # run before the evidence guard: manifest+shard files ARE the
        # evidence a fresh reader world restores from.
        adopted = self._adopt_sharded_collective() or adopted
        if (not adopted and self._commit_id == 0
                and self._durable_evidence()):
            raise StateSyncError(
                "durable commit evidence exists but no valid blob "
                "could be restored (all torn/corrupt?); refusing to "
                "train from reinitialized state — inspect "
                "HOROVOD_STATE_SPILL_DIR")
        if not basics.is_initialized() or basics.size() <= 1:
            return
        from ..jax.functions import broadcast_object
        root = self._elect_sync_root()
        self._sync_root = root
        synced = broadcast_object(
            {"attrs": self._public_attrs(), "commit_id": self._commit_id},
            root_rank=root, name="elastic.ObjectState")
        synced_commit = int(synced.get("commit_id", 0))
        # Blank/stale-root guard, independent of how the root was
        # chosen: a sync may fast-forward this rank or hold it still,
        # never rewind it.
        if synced_commit < self._commit_id:
            raise StateSyncError(
                "sync from root rank %d would regress this rank from "
                "commit %d to %d; refusing to overwrite progress with "
                "a blank or stale root" % (root, self._commit_id,
                                           synced_commit))
        for k, v in synced.get("attrs", {}).items():
            setattr(self, k, v)
        self._commit_id = synced_commit
        self.save()


class JaxState(ObjectState):
    """Pytree-aware elastic state (the TorchState equivalent for JAX):
    array-pytree attributes (params, opt_state, ...) are snapshotted to
    host numpy on commit and broadcast leaf-wise on sync; scalar
    attributes (epoch, batch, ...) ride the ObjectState path.

    Example::

        state = hvd.elastic.JaxState(params=params, opt_state=opt_state,
                                     epoch=0, batch=0)

        @hvd.elastic.run
        def train(state):
            for state.epoch in range(state.epoch, epochs):
                ...
                state.commit()
    """

    def __init__(self, **kwargs):
        import jax
        self._jax = jax
        self._tree_attrs = [k for k, v in kwargs.items()
                            if self._is_tree(v)]
        super().__init__(**kwargs)

    @staticmethod
    def _is_tree(v) -> bool:
        import jax
        leaves = jax.tree.leaves(v)
        return bool(leaves) and all(
            hasattr(l, "shape") and hasattr(l, "dtype") for l in leaves)

    def _public_attrs(self) -> Dict[str, Any]:
        return {k: v for k, v in self.__dict__.items()
                if not k.startswith("_") and k not in self._tree_attrs}

    def save(self):
        super().save()
        self._saved_trees = {
            k: self._jax.tree.map(lambda x: np.asarray(x),
                                  getattr(self, k))
            for k in self._tree_attrs}

    def restore(self):
        super().restore()
        for k, tree in self._saved_trees.items():
            setattr(self, k, self._jax.tree.map(np.copy, tree))

    def _spill_payload(self) -> Dict[str, Any]:
        payload = super()._spill_payload()
        payload["trees"] = self._saved_trees
        return payload

    def _load_payload(self, payload: Dict[str, Any]):
        self._saved_trees = payload.get("trees", {})
        super()._load_payload(payload)

    def sync(self):
        super().sync()
        if not basics.is_initialized() or basics.size() <= 1:
            return
        # Same elected root as the attribute broadcast: pytrees from
        # anyone else could mix two ranks' training states.
        root = self._sync_root if self._sync_root is not None else 0
        from ..jax.functions import broadcast_parameters
        for k in self._tree_attrs:
            setattr(self, k, broadcast_parameters(getattr(self, k),
                                                  root_rank=root))
        self.save()


def _reset_and_reinit(min_epoch=None, timeout=None):
    """Tear down the old world and join the new one (reference:
    shutdown → driver re-rendezvous → init).  ``min_epoch`` refuses
    stale assignments (see WorkerNotificationManager.rendezvous);
    ``timeout`` caps the rendezvous poll — the caller passes the
    REMAINDER of its one end-to-end deadline, so retries never reset
    the clock."""
    try:
        basics.shutdown()
    except Exception:  # noqa: BLE001 — old world may already be broken
        LOG.debug("shutdown of old world failed", exc_info=True)
    nm = notification_manager()
    if nm.active:
        info = nm.rendezvous(timeout=timeout, min_epoch=min_epoch)
        install_assignment(info)
    basics.init()


def _is_stall_abort(exc: BaseException) -> bool:
    """Did this collective failure come from the stall-shutdown
    threshold?  The in-process engine chains the StallError as the
    cause; the native core surfaces its Aborted status as message text
    ('stall shutdown threshold exceeded', operations.cc) — both planes
    must take the drain exit, not the blacklist-churning crash."""
    return (isinstance(exc.__cause__, StallError)
            or "stall shutdown threshold" in str(exc).lower())


def _stall_abort(state: State, exc: BaseException):
    """A collective crossed ``HOROVOD_STALL_SHUTDOWN_TIME_SECONDS``:
    the engine already error-completed the outstanding handles, so the
    in-memory state is exactly the last commit.  Leave through the
    drain path — committed-then-abort — instead of a hard crash: a
    stall usually means a PEER died, and blacklist-churning THIS
    (healthy) host for it would punish the wrong machine.  Raises
    :class:`WorkerDrained`."""
    nm = notification_manager()
    LOG.error("stall crossed the shutdown threshold (%s); aborting at "
              "the last commit via the drain protocol", exc)
    nm.request_drain(
        "stall shutdown threshold (HOROVOD_STALL_SHUTDOWN_TIME_SECONDS)")
    try:
        state.restore()
    except Exception:  # noqa: BLE001 — exiting anyway, keep it loud-free
        LOG.debug("restore before stall abort failed", exc_info=True)
    nm.send_drain_notice(commit_id=getattr(state, "_commit_id", 0))
    nm.arm_drain_exit(min(5.0, preempt_grace_secs()))
    raise WorkerDrained() from exc


def run(func):
    """Elastic retry decorator: ``hvd.elastic.run(train)(state, ...)``
    (reference ``run_fn`` in horovod/common/elastic.py)."""

    @functools.wraps(func)
    def wrapper(state: State, *args, **kwargs):
        nm = notification_manager()
        nm.init()
        # SIGTERM (cloud preemption, planned shutdown) enters the
        # drain protocol: finish the step, commit, notify, exit
        # distinguished — instead of dying mid-step as a "crash".
        install_preemption_handler()
        if not basics.is_initialized():
            _reset_and_reinit()
        skip_sync = False
        first = True
        while True:
            if not first:
                state.on_reset()
            first = False
            try:
                if not skip_sync:
                    state.sync()
                result = func(state, *args, **kwargs)
                # A crash-adopted driver holds no proc handle for this
                # worker, so a clean return must announce itself — the
                # reaped exit code 0 only exists for owned processes.
                nm.send_finished(
                    commit_id=getattr(state, "_commit_id", 0))
                return result
            except StallError as exc:
                _stall_abort(state, exc)
            except HorovodInternalError as exc:
                if _is_stall_abort(exc):
                    _stall_abort(state, exc)
                LOG.warning("collective failed (%s); restoring last "
                            "commit and re-rendezvousing", exc)
                state.restore()
                skip_sync = False
            except HostsUpdatedInterrupt as exc:
                LOG.info("hosts updated; re-rendezvousing")
                skip_sync = exc.skip_sync
            except WorkerStopped:
                raise
            # The world this worker just left is broken or superseded:
            # only an assignment from a NEWER driver epoch is
            # acceptable (a stale one would re-init a world containing
            # the dead member and block until the runtime's init
            # deadline kills the survivor).
            need_epoch = int(os.environ.get(
                "HOROVOD_ELASTIC_EPOCH", "0")) + 1
            # Re-rendezvous with backoff-on-failure: init itself can
            # race a second world change.  ONE monotonic deadline
            # (HOROVOD_ELASTIC_TIMEOUT) spans every retry, backoff and
            # rendezvous poll in the rejoin — each attempt gets only
            # the REMAINDER, so the total can never exceed the
            # configured timeout (the r6 verdict found workers alive
            # 13x past it: a hardcoded 600 s outer loop around
            # env-bounded inner polls).
            deadline = time.monotonic() + elastic_timeout()
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    arm_last_resort_exit("rejoin deadline")
                    raise TimeoutError(
                        "elastic rejoin did not form a world within "
                        "HOROVOD_ELASTIC_TIMEOUT=%.0fs"
                        % elastic_timeout())
                # The deadline must bound the work INSIDE the attempt
                # too: rendezvous honors `timeout`, but a wedged
                # shutdown/init (jax.distributed.initialize against a
                # half-formed world blocks for minutes) — or an
                # injected wedge at the rejoin site — would escape
                # it.  Arm the last-resort exit BEFORE the attempt,
                # cancelled on any outcome that returns control here.
                watchdog = arm_last_resort_exit(
                    "rejoin attempt overran the deadline",
                    delay=remaining)
                try:
                    faultline.site("elastic.rejoin.reinit")
                    _reset_and_reinit(min_epoch=need_epoch,
                                      timeout=remaining)
                    break
                except WorkerStopped:
                    raise
                except Exception as exc:  # noqa: BLE001
                    if time.monotonic() > deadline:
                        arm_last_resort_exit("rejoin deadline")
                        raise
                    LOG.warning("re-init failed (%s); retrying", exc)
                    time.sleep(1.0)
                finally:
                    if watchdog is not None:
                        watchdog.cancel()

    return wrapper
