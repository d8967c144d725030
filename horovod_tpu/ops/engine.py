"""Asynchronous collective engine: enqueue -> fuse -> execute.

TPU-native re-design of the reference's C++ coordination core hot path
(``horovod/common/operations.cc`` ``BackgroundThreadLoop``/``RunLoopOnce``,
``tensor_queue.cc``, ``fusion_buffer_manager.cc``): callers enqueue named
tensors and get an async handle; a background cycle thread wakes every
``HOROVOD_CYCLE_TIME`` ms, drains the queue, *fuses* small same-typed
allreduces into one flat buffer (up to ``HOROVOD_FUSION_THRESHOLD`` bytes,
padded to power-of-two buckets so the compiled-executable cache hits), runs
one XLA collective per fused group, scatters results back, and resolves the
handles.

In the single-controller SPMD world "negotiation" is trivial (one process
knows all readiness), so the controller concern collapses into this engine;
the full rank-0 negotiation protocol lives in the C++ TCP core
(``horovod_tpu/core``) used by the multi-process mode.  The engine still
records NEGOTIATE/QUEUE/FUSE/EXEC phases in the timeline so traces read
like the reference's.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.profiler
import jax.numpy as jnp
import numpy as np

from ..common import faultline, metrics
from ..common.config import Config
from ..utils.stall_inspector import StallInspector
from ..utils.timeline import Timeline
from . import fastpath, xla_ops
from .executable_cache import ExecutableCache
from .xla_ops import MeshCollectives

LOG = logging.getLogger("horovod_tpu")

_OP_ALLREDUCE = "allreduce"
_OP_ALLGATHER = "allgather"
_OP_BROADCAST = "broadcast"
_OP_ALLTOALL = "alltoall"
_OP_REDUCESCATTER = "reducescatter"
_OP_BARRIER = "barrier"


class HorovodInternalError(RuntimeError):
    """A collective failed (reference parity: surfaces to elastic mode)."""


class CollectiveDeadlineExceeded(HorovodInternalError):
    """A negotiated group outlived its per-collective deadline
    (HOROVOD_COLLECTIVE_TIMEOUT_SECS) and was error-completed.

    A HorovodInternalError subclass on purpose: elastic's run() loop
    must treat deadline expiry as a recoverable fault and restore from
    the last committed spill.  Its message must never contain the
    stall inspector's abort text ("stall shutdown threshold") — that
    phrase routes elastic to the DRAIN exit instead of restore."""


class CollectiveHandle:
    """Async completion handle (reference: torch handle_manager.cc idea)."""

    __slots__ = ("_event", "_result", "_error", "name")

    def __init__(self, name: str):
        self.name = name
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def _set_result(self, value):
        self._result = value
        self._event.set()

    def _set_error(self, exc: BaseException):
        self._error = exc
        self._event.set()

    def poll(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                "collective %r did not complete in %s s" % (self.name, timeout))
        if self._error is not None:
            raise HorovodInternalError(str(self._error)) from self._error
        return self._result


class _Entry:
    __slots__ = ("name", "op_type", "payload", "red_op", "prescale",
                 "postscale", "root_rank", "splits", "process_set_id",
                 "handle", "enqueue_t", "nbytes", "joined_idx")

    def __init__(self, name, op_type, payload, red_op, prescale, postscale,
                 root_rank, splits, process_set_id, handle, nbytes,
                 joined_idx=()):
        self.name = name
        self.op_type = op_type
        self.payload = payload
        self.red_op = red_op
        self.prescale = prescale
        self.postscale = postscale
        self.root_rank = root_rank
        self.splits = splits
        self.process_set_id = process_set_id
        self.handle = handle
        self.enqueue_t = time.monotonic()
        self.nbytes = nbytes
        # Joined-rank snapshot taken at ENQUEUE time: a later join()
        # must not retroactively zero (or reject) ops submitted while
        # every rank was still in-data.
        self.joined_idx = tuple(joined_idx)


def _fp_slot_sig(e: "_Entry") -> tuple:
    """One entry's frozen-schedule slot signature.  Names are NOT part
    of it on purpose: steady-state training loops enqueue the same
    tensors in the same order every step but often with step-suffixed
    names, and the reference's response cache keys on shape/type for
    the same reason.  Position in the cycle is the identity."""
    return (e.op_type, e.process_set_id, str(e.payload.dtype), e.red_op,
            float(e.prescale), float(e.postscale), e.joined_idx,
            tuple(e.payload.shape), int(e.nbytes))


def _bucket(n: int) -> int:
    """Pad fused flat length to a power-of-two bucket (>=1024) so compiled
    executables are reused across steps with slightly different groupings —
    static shapes are what keep XLA/MXU happy."""
    b = 1024
    while b < n:
        b <<= 1
    return b


class CollectiveEngine:
    """Background-cycle fusion engine over one device list."""

    def __init__(self, devices, config: Config, timeline: Timeline,
                 process_set_resolver: Callable[[int], List[int]]):
        self.devices = list(devices)
        self.size = len(self.devices)
        self.config = config
        self.timeline = timeline
        self._resolve_process_set = process_set_resolver
        self.cache = ExecutableCache(config.cache_capacity)
        # Process-set mesh memo: populated lazily from BOTH the caller
        # plane (enqueue path) and the cycle thread.
        self._collectives: Dict[int, MeshCollectives] = {}  # graftlint: guarded-by=_lock
        self._queue: List[_Entry] = []  # graftlint: guarded-by=_lock
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        # Poison/stop flag: set under the lock so the notify in
        # shutdown() can't race the cycle thread's wait predicate.
        self._shutdown = False  # graftlint: guarded-by=_lock
        self._cycle_count = 0  # graftlint: owned-by=hvd-tpu-cycle
        # Monotonic collective-group id: every dispatched execution
        # (fused chunk or single op) gets one; the same id tags the
        # group's timeline EXEC events (args.group) and the
        # engine_last_group_id gauge, correlating trace and metrics.
        # Guarded by its own leaf lock since r22: frozen fast-path
        # buckets dispatch on the CALLER thread, so the cycle thread
        # no longer owns the sequence.
        self._gid_lock = threading.Lock()
        self._group_seq = 0  # graftlint: guarded-by=_gid_lock
        # Fixed unlabeled series resolved ONCE: the enqueue/cycle hot
        # paths must pay only the .inc()/.set() lock round trip, not a
        # per-call name lookup + label-tuple build.
        self._m_cycles = metrics.counter("engine_cycles_total")
        self._m_cycle_seconds = metrics.histogram("engine_cycle_seconds")
        self._m_queue_depth = metrics.gauge("engine_queue_depth")
        self._m_bytes_submitted = metrics.counter(
            "engine_bytes_submitted_total")
        self._m_bytes_fused = metrics.counter("engine_bytes_fused_total")
        self._m_tensors_fused = metrics.counter(
            "engine_tensors_fused_total")
        self._m_cache_hits = metrics.gauge("exec_cache_hits")
        self._m_cache_misses = metrics.gauge("exec_cache_misses")
        self._m_last_group = metrics.gauge("engine_last_group_id")
        self.stall_inspector = StallInspector(
            warning_secs=config.stall_warning_secs,
            shutdown_secs=config.stall_shutdown_secs,
            enabled=not config.stall_check_disable)
        self.parameter_manager = None  # installed by basics when autotuning
        # One-shot latch: the converged GP point is staged into the
        # plan cache exactly once (cycle-thread only).
        self._pm_converged_noted = False  # graftlint: owned-by=hvd-tpu-cycle
        # Ranks marked out-of-data (reference JoinOp): they contribute
        # zeros to allreduces until every rank has joined.  Ordered so
        # finalize can report the LAST rank to join, like the core.
        self._joined: List[int] = []  # graftlint: guarded-by=_lock
        # -- steady-state fast path (frozen schedule, ISSUE 19) --
        # Staging state for the current frozen cycle: callers match
        # entries against the frozen slots positionally and dispatch
        # each overlap bucket inline the instant it fills — no cycle-
        # thread handoff, no cycle-time wait.  _fp_lock is reentrant
        # (a mismatch thaw flushes from under the staging section) and
        # is always taken BEFORE _lock/_wake (lock order, never after).
        self._fp_lock = threading.RLock()
        self._fp_pending: List[_Entry] = []  # graftlint: guarded-by=_fp_lock
        self._fp_idx = 0  # graftlint: guarded-by=_fp_lock
        self._fp_t = 0.0  # graftlint: guarded-by=_fp_lock
        self._fp = fastpath.ScheduleFreezer(
            warm_cycles=config.fast_path_warm_cycles,
            enabled=config.fast_path, spmd=False, plane_name="eager",
            on_thaw=self._fp_flush, stage_lock=self._fp_lock)
        fastpath.register(self._fp)
        self._m_fp_frozen = metrics.counter("fastpath_frozen_cycles_total")
        self._m_fp_bucket = metrics.histogram(
            "engine_overlap_bucket_seconds")
        self._thread = threading.Thread(
            target=self._loop, name="hvd-tpu-cycle", daemon=True)
        self._thread.start()

    # -- join (zero contribution, reference JoinOp) ------------------------

    def mark_joined(self, ranks):
        """Mark world ranks as out of data; their rows of every
        subsequent stacked allreduce payload are zeroed (the reference's
        joined ranks contribute zeros, ``operations.cc`` JoinOp path)."""
        # A join changes the payload the frozen schedule would
        # dispatch (zeroed rows): thaw before mutating membership.
        self._fp.thaw("membership", detail="rank(s) %s joined"
                      % list(ranks))
        with self._lock:
            for r in ranks:
                r = int(r)
                if not 0 <= r < self.size:
                    raise ValueError("join rank %d outside world [0, %d)"
                                     % (r, self.size))
                if r not in self._joined:
                    self._joined.append(r)

    def finalize_join(self) -> int:
        """All remaining ranks join now (in rank order); clears the
        joined set and returns the last rank to join, like the core's
        ``hvd_tcp_join``."""
        with self._lock:
            joined, self._joined = self._joined, []
        remaining = [r for r in range(self.size) if r not in joined]
        if remaining:
            return remaining[-1]
        return joined[-1] if joined else self.size - 1

    def _joined_member_indices(self, process_set_id) -> List[int]:
        with self._lock:
            joined = list(self._joined)
        if not joined:
            return []
        members = self._resolve_process_set(process_set_id)
        if members is None:
            members = list(range(self.size))
        return [i for i, g in enumerate(members) if g in joined]

    # -- process-set meshes ------------------------------------------------

    def collectives_for(self, process_set_id: int) -> MeshCollectives:
        # Reached from the caller plane (enqueue_alltoall sizing) AND
        # the cycle thread (_run_cycle): memoize under the lock so two
        # racing first-touches can't build two meshes for one set.
        with self._lock:
            mc = self._collectives.get(process_set_id)
            if mc is None:
                ranks = self._resolve_process_set(process_set_id)
                devs = (self.devices if ranks is None
                        else [self.devices[r] for r in ranks])
                mc = MeshCollectives(devs, cache=self.cache,
                                     name="ps%d" % process_set_id)
                self._collectives[process_set_id] = mc
            return mc

    def invalidate_process_set(self, process_set_id: int):
        self._fp.thaw("membership",
                      detail="process set %d invalidated"
                      % process_set_id)
        with self._lock:
            self._collectives.pop(process_set_id, None)

    # -- enqueue API -------------------------------------------------------

    def _enqueue(self, name, op_type, payload, red_op=xla_ops.SUM,
                 prescale=1.0, postscale=1.0, root_rank=0, splits=None,
                 process_set_id=0, nbytes=0) -> CollectiveHandle:
        if self._shutdown:
            raise HorovodInternalError("engine is shut down")
        joined_idx = self._joined_member_indices(process_set_id)
        if joined_idx and op_type == _OP_ALLREDUCE and \
                red_op not in (xla_ops.SUM, xla_ops.AVERAGE):
            # Zero is Sum's reduction identity; Average is handled by
            # dividing by the live-contributor count at execution.  For
            # Min/Max/Product a zero contribution from joined ranks would
            # silently corrupt the result (mirrors the Adasum guard in
            # op_manager.py).
            raise HorovodInternalError(
                "allreduce %r with op=%s submitted while ranks are joined; "
                "zero-contribution join is only supported for Sum/Average"
                % (name, red_op))
        handle = CollectiveHandle(name)
        e = _Entry(name, op_type, payload, red_op, prescale, postscale,
                   root_rank, splits, process_set_id, handle, nbytes,
                   joined_idx=joined_idx)
        self.timeline.negotiate_start(name, op_type)
        self.stall_inspector.record_enqueue(name)
        self._m_bytes_submitted.inc(nbytes)
        if self._fp_stage(e):
            return handle
        with self._wake:
            self._queue.append(e)
            self._wake.notify()
        return handle

    def enqueue_allreduce(self, name, stacked, red_op, prescale, postscale,
                          process_set_id) -> CollectiveHandle:
        arr = xla_ops.host_or_device(stacked)
        return self._enqueue(name, _OP_ALLREDUCE, arr, red_op=red_op,
                             prescale=prescale, postscale=postscale,
                             process_set_id=process_set_id,
                             nbytes=arr.nbytes // max(arr.shape[0], 1))

    def enqueue_allgather(self, name, per_rank, process_set_id):
        return self._enqueue(name, _OP_ALLGATHER, per_rank,
                             process_set_id=process_set_id)

    def enqueue_broadcast(self, name, stacked, root_rank, process_set_id):
        return self._enqueue(name, _OP_BROADCAST,
                             xla_ops.host_or_device(stacked),
                             root_rank=root_rank,
                             process_set_id=process_set_id)

    def enqueue_alltoall(self, name, stacked, splits, process_set_id):
        return self._enqueue(name, _OP_ALLTOALL, stacked, splits=splits,
                             process_set_id=process_set_id)

    def enqueue_reducescatter(self, name, stacked, red_op, process_set_id):
        return self._enqueue(name, _OP_REDUCESCATTER,
                             xla_ops.host_or_device(stacked),
                             red_op=red_op, process_set_id=process_set_id)

    def enqueue_barrier(self, name, process_set_id):
        return self._enqueue(name, _OP_BARRIER, None,
                             process_set_id=process_set_id)

    # -- steady-state fast path (frozen schedule, ISSUE 19) ----------------

    def _fp_profile(self, batch: List[_Entry]):
        """Freezable profile of one negotiated cycle, or None.  Only
        pure-allreduce cycles sharing ONE fuse key with no joined
        ranks freeze: an overlap bucket is a fused dispatch unit, and
        mixed keys (or a membership transition) cannot fuse."""
        keys = set()
        for e in batch:
            if e.op_type != _OP_ALLREDUCE or e.joined_idx:
                return None
            keys.add((e.process_set_id, str(e.payload.dtype), e.red_op,
                      float(e.prescale), float(e.postscale)))
            if len(keys) > 1:
                return None
        return tuple(_fp_slot_sig(e) for e in batch)

    def _fp_payload(self, batch: List[_Entry], prof) -> dict:
        """The schedule cached at freeze time: the positional slot
        signatures plus the overlap-bucket partition (contiguous,
        balanced by bytes, capped at the fusion threshold)."""
        ends = fastpath.bucket_ends(
            [e.nbytes for e in batch], self.config.overlap_buckets,
            self.config.fusion_threshold_bytes)
        return {"sig": fastpath.schedule_sig(prof),
                "slots": list(prof), "ends": ends}

    def _fp_stage(self, e: _Entry) -> bool:  # graftlint: schedule-entry=fastpath -- frozen-schedule bucket dispatch of the eager plane (negotiation skipped)
        """Frozen-schedule staging (caller thread).  Match ``e``
        against the next frozen slot; the instant an overlap bucket's
        last tensor lands, dispatch that bucket INLINE — the XLA
        dispatch is async, so the caller keeps producing gradients for
        later buckets while this one's collective runs, and the
        negotiation queue, cycle thread and cycle-time wait are all
        skipped.  A mismatch thaws loudly and falls back (returns
        False: the caller requeues ``e`` on the negotiation path)."""
        if self._fp.frozen() is None:
            return False
        with self._fp_lock:
            fs = self._fp.frozen()
            if fs is None:
                return False
            slots = fs["slots"]
            if (self._fp_idx >= len(slots)
                    or _fp_slot_sig(e) != slots[self._fp_idx]):
                self._fp.thaw(
                    "shape", detail="entry %r does not match frozen "
                    "slot %d" % (e.name, self._fp_idx))
                return False
            self.timeline.negotiate_end(e.name)
            self._fp_pending.append(e)
            self._fp_t = time.monotonic()
            self._fp_idx += 1
            if self._fp_idx not in fs["ends"]:
                return True
            if fastpath.stale_dispatch_seam():
                # Injected stale dispatch: the frozen schedule must
                # not be trusted — thaw loudly; the flush pushes this
                # bucket's tensors back through full negotiation
                # (correct values, no hang).
                self._fp.thaw(
                    "staleness", detail="injected stale dispatch "
                    "(engine.fastpath.stale_dispatch)")
                return True
            pending, self._fp_pending = self._fp_pending, []
            done = self._fp_idx == len(slots)
            if done:
                self._fp_idx = 0
            t0 = time.monotonic()
            self._execute_fused_allreduce(pending)
            self._m_fp_bucket.observe(time.monotonic() - t0)
            if done:
                # A frozen cycle is counted here, NOT in
                # engine_cycles_total: exactly one of the two moves
                # per cycle, and the exec-cache gauges refresh in the
                # same breath so levers.metrics never reads a cached
                # dispatch as both a cache hit and a negotiation
                # cycle.
                self._m_fp_frozen.inc()
                hits, misses = self.cache.stats()
                self._m_cache_hits.set(hits)
                self._m_cache_misses.set(misses)
            return True

    def _fp_flush(self, _payload: dict, _reason: str):
        """Thaw fallback (any thread; runs under _fp_lock via the
        freezer): staged-but-undispatched entries re-enter the
        negotiation queue in program order, so their handles resolve
        through the normal cycle path."""
        with self._fp_lock:
            pending, self._fp_pending = self._fp_pending, []
            self._fp_idx = 0
        if not pending:
            return
        with self._wake:
            self._queue.extend(pending)
            self._wake.notify()

    def _fp_idle_check(self):
        """Safety valve (cycle thread): a frozen cycle staged
        PARTIALLY and went quiet — the app's per-step entry list
        shrank without tripping a slot mismatch.  Waiting forever
        would hang a caller blocked on a staged handle; thaw and
        negotiate the stragglers instead."""
        if self._fp.frozen() is None:
            return
        with self._fp_lock:
            stale = bool(self._fp_pending) and (
                time.monotonic() - self._fp_t
                > max(0.05, 4 * self.config.cycle_time_ms / 1e3))
            if stale:
                self._fp.thaw(
                    "shape", detail="partial frozen cycle (%d of %d "
                    "slots) flushed back to negotiation"
                    % (self._fp_idx,
                       len((self._fp.frozen() or {}).get("slots", ()))))

    # -- background loop ---------------------------------------------------

    def _loop(self):
        while True:
            with self._wake:
                if not self._queue and not self._shutdown:
                    # Idle coarsening: with nothing queued AND nothing
                    # outstanding there is no work the cycle tick could
                    # start — sleep long (enqueue notifies instantly).
                    # An idle engine waking every few ms steals the GIL
                    # from the jit dispatch loop (measured ~1 ms/step
                    # on the ResNet bench with a 5 ms tick).
                    idle_t = (self.config.cycle_time_ms / 1e3
                              if self.stall_inspector.has_outstanding()
                              else 0.5)
                    self._wake.wait(timeout=idle_t)
                if self._shutdown and not self._queue:
                    return
                batch, self._queue = self._queue, []
            self._fp_idle_check()
            self._cycle_count += 1
            self.timeline.mark_cycle(self._cycle_count)
            if batch:
                self._m_cycles.inc()
                self._m_queue_depth.set(len(batch))
                t0 = time.monotonic()
                _, misses0 = self.cache.stats()
                nbytes = sum(e.nbytes for e in batch)
                self._run_cycle(batch)
                self._m_cycle_seconds.observe(time.monotonic() - t0)
                hits, misses = self.cache.stats()
                self._m_cache_hits.set(hits)
                self._m_cache_misses.set(misses)
                # A cycle that compiled a new XLA executable measures
                # the compiler, not communication; feeding it to the
                # tuner would bias the early GP samples (the reference
                # resets after HOROVOD_AUTOTUNE_WARMUP for the same
                # reason).
                compiled = misses != misses0
                if self.parameter_manager is not None and not compiled:
                    self.parameter_manager.observe(
                        nbytes, time.monotonic() - t0)
                    self.config.fusion_threshold_bytes = (
                        self.parameter_manager.fusion_threshold)
                    self.config.cycle_time_ms = (
                        self.parameter_manager.cycle_time_ms)
                    if (self.parameter_manager.frozen
                            and self.parameter_manager.samples_done > 0
                            and not self._pm_converged_noted):
                        # Stage the converged operating point for the
                        # plan cache the moment the GP pins it —
                        # convergence is only observable here, and
                        # shutdown persists whatever was staged.
                        # samples_done > 0 excludes a PM that was
                        # BORN frozen from a cache warm start: its
                        # point is cached provenance, not tuned.
                        self._pm_converged_noted = True
                        from ..utils import plancache
                        plancache.note_tuned(
                            self.parameter_manager.fusion_threshold,
                            self.parameter_manager.cycle_time_ms, True)
                # Warm counting for the steady-state fast path: one
                # identical-profile streak long enough freezes the
                # schedule (single-controller world: the freeze verdict
                # is trivially SPMD-uniform, no KV round needed).
                if self._fp.enabled and self._fp.frozen() is None:
                    prof = self._fp_profile(batch)
                    if self._fp.observe(prof):
                        with self._gid_lock:
                            gid = self._group_seq
                        self._fp.freeze(
                            self._fp_payload(batch, prof), gid)
            try:
                self.stall_inspector.check()
            except Exception as exc:  # StallError -> fail outstanding ops
                with self._wake:
                    pending, self._queue = self._queue, []
                for e in pending:
                    e.handle._set_error(exc)

    def _run_cycle(  # graftlint: schedule-entry=eager -- per-cycle collective order of the eager TCP-core plane
            self, batch: List[_Entry]):
        faultline.site("engine.cycle.pre")
        # Group allreduces for fusion: (process set, dtype, red_op, scales).
        fuse_groups: Dict[tuple, List[_Entry]] = {}
        singles: List[_Entry] = []
        for e in batch:
            self.timeline.negotiate_end(e.name)
            if e.op_type == _OP_ALLREDUCE:
                # joined_idx is part of the key: entries straddling a
                # join() must not fuse, or the Average live-contributor
                # divisor below would be wrong for part of the bucket.
                k = (e.process_set_id, str(e.payload.dtype), e.red_op,
                     float(e.prescale), float(e.postscale), e.joined_idx)
                fuse_groups.setdefault(k, []).append(e)
            else:
                singles.append(e)
        for key, group in fuse_groups.items():
            # Respect the fusion threshold: chunk greedy-first-fit in order.
            chunk: List[_Entry] = []
            chunk_bytes = 0
            for e in group:
                if chunk and chunk_bytes + e.nbytes > \
                        self.config.fusion_threshold_bytes:
                    self._execute_fused_allreduce(chunk)
                    chunk, chunk_bytes = [], 0
                chunk.append(e)
                chunk_bytes += e.nbytes
            if chunk:
                self._execute_fused_allreduce(chunk)
        for e in singles:
            self._execute_single(e)

    def _next_group(self) -> int:
        """Next collective-group id (cycle thread OR a caller thread
        dispatching a frozen bucket): tags the group's timeline EXEC
        span and the engine_last_group_id gauge so the trace and
        metrics planes correlate."""
        with self._gid_lock:
            self._group_seq += 1
            gid = self._group_seq
        self._m_last_group.set(gid)
        return gid

    def _execute_fused_allreduce(self, entries: List[_Entry]):
        names = [e.name for e in entries]
        # xprof span (the reference's NVTX op range, nvtx_op_range.cc):
        # collective executions show up named in jax.profiler traces
        with jax.profiler.TraceAnnotation(
                "hvd.allreduce[%d tensors]" % len(entries)):
            self._execute_fused_allreduce_inner(entries, names)

    def _execute_fused_allreduce_inner(self, entries: List[_Entry],
                                       names: List[str]):
        try:
            mc = self.collectives_for(entries[0].process_set_id)
            size = mc.size

            def zero_joined(stacked, joined_idx):
                # Joined ranks contribute zeros (reference JoinOp).
                # Uses the entry's enqueue-time snapshot, so join() is
                # never retroactive.
                if not joined_idx:
                    return stacked
                return mc.shard_stacked(stacked).at[
                    jnp.asarray(joined_idx)].set(0)

            # Average over live contributors: zero is not Average's
            # identity, so dividing by the full member count would bias
            # the result toward zero.  Execute as Sum with 1/live folded
            # into postscale (mirrors the controller's join rewrite).
            e0 = entries[0]
            red_op, postscale = e0.red_op, float(e0.postscale)
            if e0.joined_idx and red_op == xla_ops.AVERAGE:
                live = size - len(e0.joined_idx)
                if live <= 0:
                    raise HorovodInternalError(
                        "Average allreduce with every member joined")
                red_op, postscale = xla_ops.SUM, postscale / live

            if len(entries) == 1 and entries[0].payload.ndim >= 1:
                e = entries[0]
                self.timeline.activity_start(
                    e.name, "EXEC_ALLREDUCE",
                    args={"group": self._next_group()})
                out = mc.allreduce(
                    zero_joined(e.payload, e.joined_idx), red_op,
                    float(e.prescale), postscale)
                self.timeline.activity_end(e.name)
                self.stall_inspector.record_done(e.name)
                e.handle._set_result(out)
                return
            # The whole fusion cycle is ONE compiled program (flatten +
            # zero joined rows + concat into the padded bucket + the
            # collective + per-entry slices): XLA manages the fusion
            # buffer as a compiler scratch instead of the engine
            # dispatching separate concat/collective/slice programs
            # (the reference's persistent fusion buffer, the XLA way).
            self._m_bytes_fused.inc(sum(e.nbytes for e in entries))
            self._m_tensors_fused.inc(len(entries))
            self.timeline.activity_start_all(
                names, "EXEC_FUSED_ALLREDUCE",
                args={"group": self._next_group()})
            total = sum(
                int(np.prod(e.payload.shape[1:], dtype=np.int64))
                for e in entries)
            outs = mc.fused_allreduce(
                [e.payload for e in entries], red_op,
                float(e0.prescale), postscale,
                [e.joined_idx for e in entries], _bucket(total))
            self.timeline.activity_end_all(names)
            for e, out in zip(entries, outs):
                self.stall_inspector.record_done(e.name)
                e.handle._set_result(out)
        except Exception as exc:  # noqa: BLE001 - propagate to handles
            LOG.error("fused allreduce failed: %s", exc)
            for e in entries:
                self.stall_inspector.record_done(e.name)
                e.handle._set_error(exc)

    def _execute_single(self, e: _Entry):
        try:
            mc = self.collectives_for(e.process_set_id)
            if e.op_type != _OP_BARRIER and e.joined_idx:
                # Mirror the controller: only allreduce can proceed with
                # a zero contribution from joined ranks; anything else
                # would deadlock or silently mis-shape.
                raise HorovodInternalError(
                    "%s %r submitted while ranks are joined; only "
                    "allreduce supports zero-contribution join"
                    % (e.op_type, e.name))
            self.timeline.activity_start(
                e.name, "EXEC_" + e.op_type.upper(),
                args={"group": self._next_group()})
            # xprof span (reference NVTX op range, nvtx_op_range.cc)
            with jax.profiler.TraceAnnotation("hvd.%s" % e.op_type):
                if e.op_type == _OP_ALLGATHER:
                    out = mc.allgather(e.payload)
                elif e.op_type == _OP_BROADCAST:
                    out = mc.broadcast(e.payload, e.root_rank)
                elif e.op_type == _OP_ALLTOALL:
                    out = mc.alltoall(e.payload, e.splits)
                elif e.op_type == _OP_REDUCESCATTER:
                    d0 = e.payload.shape[1]
                    if d0 % mc.size:
                        # Uneven rows: full reduce on the mesh, then
                        # slice the core's chunk layout — rank j gets
                        # d0//n + (1 if j < d0%n) rows, earlier ranks
                        # larger (operations.cc REDUCESCATTER chunking).
                        red = mc.allreduce(e.payload, e.red_op)
                        rows, offs = xla_ops.uneven_chunks(d0, mc.size)
                        out = [red[o:o + c] for c, o in zip(rows, offs)]
                    else:
                        out = mc.reducescatter(e.payload, e.red_op)
                elif e.op_type == _OP_BARRIER:
                    out = mc.barrier()
                else:
                    raise NotImplementedError(e.op_type)
            self.timeline.activity_end(e.name)
            self.stall_inspector.record_done(e.name)
            e.handle._set_result(out)
        except Exception as exc:  # noqa: BLE001
            LOG.error("%s %r failed: %s", e.op_type, e.name, exc)
            self.stall_inspector.record_done(e.name)
            e.handle._set_error(exc)

    # -- shutdown ----------------------------------------------------------

    def shutdown(self):
        # Flush any staged frozen work back into the queue FIRST so
        # the cycle thread drains it before exiting (the world is
        # ending: membership is the honest reason).
        self._fp.thaw("membership", detail="engine shutdown")
        fastpath.unregister(self._fp)
        with self._wake:
            self._shutdown = True
            self._wake.notify()
        self._thread.join(timeout=10.0)
