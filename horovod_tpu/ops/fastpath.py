"""Steady-state fast path: frozen negotiated schedules (the upstream
``response_cache.cc`` idea taken one step further).

Upstream Horovod observes that once tensor shapes stabilize, per-step
negotiation dominates, and coordinates steady state through a bit
vector over cached responses instead of re-gathering full requests
(Sergeev & Del Balso, arXiv:1802.05799).  This module is our version
of that cache with the remaining coordination removed too: after
``HOROVOD_FAST_PATH_WARM_CYCLES`` *identical* negotiated cycles (same
tensor multiset, shapes, dtypes, reduction parameters, membership) the
engine FREEZES the response schedule.  A frozen engine dispatches
collectives straight off the cached schedule — request gather, fusion
planning and response broadcast are all skipped — and carves the fused
payload into ``HOROVOD_OVERLAP_BUCKETS`` staging buckets, each
dispatched the instant its last tensor lands so early buckets'
collectives overlap later gradient production (the bucketed
comm/compute overlap lever of Li et al., arXiv:2006.15704).

A frozen schedule must never mask a change: every loud-invalidation
source THAWS it back to full negotiation —

- ``shape``      a staged tensor no longer matches its frozen slot
                 (also the partial-cycle safety valve);
- ``membership`` process-set invalidation, join, elastic resize,
                 engine shutdown, or an unexpected negotiated record;
- ``staleness``  a :meth:`PlanController.invalidate` trip (and the
                 injected ``engine.fastpath.stale_dispatch`` fault);
- ``route``      a degraded-route demote/promote verdict
                 (``resilience._apply_route``);
- ``deadline``   a per-collective deadline expiry.

Thaws are loud: a warning log, ``fastpath_thaws_total{reason}`` and a
``fastpath_thaw`` journal event carrying the frozen schedule's group
id for timeline correlation.

The freeze decision is SPMD-uniform.  Multi-process engines route it
through the rendezvous-KV record protocol (rank-0 verdict, the plan-
staleness/degraded-route pattern): every member's warm streak trips at
the same negotiated-record index because records are coordinator-
broadcast, rank 0 publishes ``{seq, sig, freeze}`` under the topology
fingerprint and members block for a record covering their own proposal
seq — a frozen rank and a negotiating rank can never coexist (the
frozen rank stops feeding the coordinator and would wedge the world).
KV-less multi-member worlds never freeze (warned once); the
single-controller in-process engine freezes locally.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..common import metrics

LOG = logging.getLogger("horovod_tpu")

# The thaw-reason label enum (docs/observability.md); thaw() rejects
# anything else so the metric's cardinality stays closed.
THAW_REASONS = ("shape", "membership", "staleness", "route", "deadline")

# Rendezvous-KV key carrying rank 0's freeze verdicts, per topology
# fingerprint (the plan-staleness record protocol).
_FREEZE_KEY = "fastpath/freeze/v%d/%s"


def stale_dispatch_seam() -> bool:
    """The frozen-schedule bucket-dispatch injection seam: a completed
    overlap bucket is about to dispatch off the frozen schedule, and a
    ``drop`` here means the schedule must be treated as stale.  Fired
    by BOTH engines' ``_fp_stage`` through this one helper so the site
    names one seam (the ``serving.replica.die`` pattern)."""
    from ..common import faultline
    return bool(faultline.site("engine.fastpath.stale_dispatch"))


def schedule_sig(profile) -> str:
    """Stable signature of one cycle profile (hashed so the KV record
    stays small; members compare signatures, never full profiles)."""
    return hashlib.sha1(repr(profile).encode()).hexdigest()[:16]


class _Frozen:
    __slots__ = ("payload", "group_id")

    def __init__(self, payload: Dict[str, Any], group_id: int):
        self.payload = payload
        self.group_id = group_id


class ScheduleFreezer:
    """Warm-streak counter + freeze/thaw state machine for one engine.

    The engine feeds :meth:`observe` one profile per negotiated cycle
    and calls :meth:`freeze` when the streak trips; callers on the
    enqueue path read :meth:`frozen` (racy fast check) and re-check it
    under ``stage_lock`` — the same lock :meth:`thaw` mutates the
    frozen latch under, so a thaw and an in-flight staging operation
    fully serialize and a thaw's ``on_thaw`` flush always sees a
    consistent staged set.
    """

    def __init__(self, warm_cycles: int, enabled: bool = True,
                 spmd: bool = False, plane_name: str = "eager",
                 on_thaw: Optional[Callable[[Dict[str, Any], str], None]]
                 = None,
                 stage_lock=None):
        self.warm_cycles = max(1, int(warm_cycles))
        self.enabled = bool(enabled)
        self.plane_name = plane_name
        self._spmd = bool(spmd)
        self._on_thaw = on_thaw
        # Streak/seq state lock (leaf: nothing is called while held).
        self._lock = threading.Lock()
        # The frozen latch is guarded by the engine's staging lock so
        # thaw-vs-stage races cannot dispatch off a dead schedule.
        self._stage_lock = (stage_lock if stage_lock is not None
                            else threading.RLock())
        self._last_profile = None  # graftlint: guarded-by=_lock
        self._streak = 0  # graftlint: guarded-by=_lock
        self._seq = 0  # freeze proposals made  # graftlint: guarded-by=_lock
        self._warned_no_kv = False  # graftlint: guarded-by=_lock
        self._frozen: Optional[_Frozen] = None  # graftlint: guarded-by=_stage_lock

    # -- read side ---------------------------------------------------------

    def frozen(self) -> Optional[Dict[str, Any]]:
        """Current frozen schedule payload (None = negotiating).  The
        bare read is the hot-path fast check; stage paths re-check
        under ``stage_lock`` before trusting it."""
        fz = self._frozen
        return fz.payload if fz is not None else None

    def frozen_group_id(self) -> Optional[int]:
        fz = self._frozen
        return fz.group_id if fz is not None else None

    @property
    def streak(self) -> int:
        with self._lock:
            return self._streak

    # -- warm counting -----------------------------------------------------

    def observe(self, profile) -> bool:
        """Feed one negotiated cycle's schedule profile (None = not
        freezable); returns True when the warm streak just tripped and
        the engine should attempt :meth:`freeze`."""
        if not self.enabled:
            return False
        with self._lock:
            if self._frozen is not None:
                return False
            if profile is None or profile != self._last_profile:
                self._last_profile = profile
                self._streak = 1 if profile is not None else 0
                return False
            self._streak += 1
            return self._streak >= self.warm_cycles

    def reset_streak(self):
        with self._lock:
            self._streak = 0
            self._last_profile = None

    # -- freeze ------------------------------------------------------------

    def freeze(self, payload: Dict[str, Any], group_id: int,
               ok: bool = True) -> bool:
        """Freeze ``payload`` (the engine's cached schedule) as of
        collective group ``group_id``.  ``ok`` is the engine's local
        eligibility gate (e.g. no in-flight negotiated work); on SPMD
        planes only rank 0's gate decides and members adopt the
        verdict.  Returns True when the schedule is now frozen."""
        if not self.enabled:
            return False
        verdict = self._agree_freeze(payload, ok) if self._spmd else ok
        if not verdict:
            # A refused proposal restarts warm counting everywhere at
            # the same cycle index (locally trivial; SPMD because the
            # verdict itself is uniform).
            self.reset_streak()
            return False
        with self._stage_lock:
            if self._frozen is None:
                self._frozen = _Frozen(dict(payload), int(group_id))
        LOG.info(
            "fast path FROZEN (%s plane): %d-slot schedule cached as of "
            "group %d after %d identical cycles — dispatch now skips "
            "negotiation until a thaw",
            self.plane_name, len(payload.get("slots", ())), group_id,
            self.warm_cycles)
        metrics.event("fastpath_freeze", plane=self.plane_name,
                      group=int(group_id), sig=payload.get("sig"),
                      slots=len(payload.get("slots", ())))
        return True

    def _agree_freeze(self, payload, ok: bool) -> bool:  # graftlint: spmd-uniform -- rank-0-decide -> KV-adopt: every member's warm streak trips at the same negotiated-record index (records are coordinator-broadcast, so the observed schedule stream is identical on every member); rank 0 publishes {seq, sig, freeze} under the fingerprint key and members block for a record covering THEIR OWN proposal seq, adopting rank 0's verdict on a signature match — freeze state can never diverge (a frozen rank stops feeding the coordinator, so a half-frozen world is the r14 hang class).  KV-less multi-member worlds never freeze (warned once) and a member that cannot reach rank 0's record raises rather than guess.
        from ..utils import plancache
        plane = plancache.world_plane()
        size = plane.size or 1
        if size <= 1:
            return ok
        if plane.kv is None:
            with self._lock:
                if not self._warned_no_kv:
                    self._warned_no_kv = True
                    LOG.warning(
                        "fast path: multi-member world with no "
                        "rendezvous KV to agree through (set "
                        "HOROVOD_RENDEZVOUS_ADDR) — schedules stay "
                        "unfrozen (a rank-local freeze would desync "
                        "the negotiation loop)")
            return False
        with self._lock:
            self._seq += 1
            seq = self._seq
        sig = payload.get("sig")
        key = _FREEZE_KEY % (plancache.SCHEMA_VERSION,
                             plane.fingerprint or "world")
        if plane.rank in (None, 0):
            plane.kv.put_json(
                key, {"seq": seq, "sig": sig, "freeze": bool(ok)})
            return bool(ok)
        deadline = time.monotonic() + 60.0
        while True:
            rec = plane.kv.get_json(key)
            if isinstance(rec, dict) and rec.get("seq", 0) >= seq:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "fast-path freeze: rank 0 never published verdict "
                    "#%d — members must adopt rank 0's freeze or not "
                    "at all (a half-frozen world wedges negotiation)"
                    % seq)
            time.sleep(0.05)
        if rec.get("seq") != seq or rec.get("sig") != sig:
            # Proposal streams diverged (this member tripped on a
            # different schedule or index than rank 0): refuse and
            # re-warm rather than freeze on a schedule rank 0 didn't
            # certify.
            LOG.warning(
                "fast-path freeze verdict mismatch (rank 0 published "
                "seq=%s sig=%s, local seq=%d sig=%s); staying thawed",
                rec.get("seq"), rec.get("sig"), seq, sig)
            return False
        if rec.get("freeze") and not ok:
            LOG.warning(
                "fast path: adopting rank 0's freeze verdict with "
                "local in-flight negotiated work still pending — an "
                "async enqueue pattern straddling the freeze point "
                "can only resolve through the collective deadline")
        return bool(rec.get("freeze"))

    # -- thaw --------------------------------------------------------------

    def thaw(self, reason: str, detail: str = "") -> bool:
        """Invalidate the frozen schedule back to full negotiation.
        Loud on purpose: warning log + ``fastpath_thaws_total{reason}``
        + a ``fastpath_thaw`` event carrying the frozen group id.
        No-op (False) when nothing is frozen."""
        if reason not in THAW_REASONS:
            raise ValueError("unknown thaw reason %r (one of %s)"
                             % (reason, ", ".join(THAW_REASONS)))
        with self._stage_lock:
            fz, self._frozen = self._frozen, None
            if fz is None:
                return False
            self.reset_streak()
            metrics.counter("fastpath_thaws_total", reason=reason).inc()
            metrics.event("fastpath_thaw", plane=self.plane_name,
                          reason=reason, group=fz.group_id,
                          sig=fz.payload.get("sig"), detail=detail)
            LOG.warning(
                "fast path THAWED (%s plane, reason=%s%s): frozen "
                "schedule of group %d (%d slot(s)) falls back to full "
                "negotiation",
                self.plane_name, reason,
                ", " + detail if detail else "", fz.group_id,
                len(fz.payload.get("slots", ())))
            if self._on_thaw is not None:
                # Still under stage_lock (reentrant): the flush sees
                # the exact staged set the thaw interrupted.
                try:
                    self._on_thaw(fz.payload, reason)
                except Exception:  # noqa: BLE001 - flush must not mask the thaw
                    LOG.exception("fast-path thaw flush failed")
        return True


# -- module registry (external invalidation planes reach engines here) -----

_REG_LOCK = threading.Lock()
_FREEZERS: List[ScheduleFreezer] = []  # graftlint: guarded-by=_REG_LOCK
# Optional provider of the native core's avoided-negotiation-round
# counter (installed by the multihost engine when the .so exports it).
_CORE_ROUNDS: Optional[Callable[[], int]] = None


def register(freezer: ScheduleFreezer):
    with _REG_LOCK:
        if freezer not in _FREEZERS:
            _FREEZERS.append(freezer)


def unregister(freezer: ScheduleFreezer):
    with _REG_LOCK:
        if freezer in _FREEZERS:
            _FREEZERS.remove(freezer)


def set_core_rounds_provider(fn: Optional[Callable[[], int]]):
    global _CORE_ROUNDS
    _CORE_ROUNDS = fn


def thaw_all(reason: str, detail: str = "") -> int:
    """Thaw every registered engine's frozen schedule (no-op on
    engines that aren't frozen).  The hook every loud-invalidation
    plane calls: plan-staleness trips, degraded-route verdicts,
    collective-deadline expiry, membership changes."""
    with _REG_LOCK:
        freezers = list(_FREEZERS)
    return sum(1 for fz in freezers if fz.thaw(reason, detail))


def reset():
    """Test hook: drop registered freezers and the core provider."""
    global _CORE_ROUNDS
    with _REG_LOCK:
        del _FREEZERS[:]
    _CORE_ROUNDS = None


def describe() -> Dict[str, Any]:
    """The ``levers.fastpath`` self-attribution block (bench.py and
    the allreduce_bw A/B leg): frozen/thaw counters from the live
    metrics plus per-plane freezer state.  Degrades to counters-only
    before/without ``hvd.init``."""
    snap = metrics.snapshot()
    thaws: Dict[str, float] = {}
    for row in (snap.get("fastpath_thaws_total") or {}).get("series", []):
        r = row.get("labels", {}).get("reason", "?")
        thaws[r] = thaws.get(r, 0.0) + float(row.get("value", 0.0))
    with _REG_LOCK:
        freezers = list(_FREEZERS)
    planes = {}
    for fz in freezers:
        planes[fz.plane_name] = {
            "enabled": fz.enabled,
            "frozen": fz.frozen() is not None,
            "warm_streak": fz.streak,
            "warm_cycles": fz.warm_cycles,
        }
    out: Dict[str, Any] = {
        "frozen_cycles_total": metrics.series_sum(
            "fastpath_frozen_cycles_total"),
        "thaws_total": sum(thaws.values()),
        "thaws_by_reason": thaws,
        "planes": planes,
    }
    if _CORE_ROUNDS is not None:
        out["core_idle_rounds_skipped"] = int(_CORE_ROUNDS())
    return out


def bucket_ends(sizes: List[int], buckets: int, cap_bytes: int
                ) -> List[int]:
    """Partition a frozen cycle's per-slot byte sizes into up to
    ``buckets`` contiguous overlap buckets (balanced by bytes, each
    additionally capped at the fusion threshold); returns the
    exclusive end index of every bucket — the staging path dispatches
    a bucket the instant the slot at ``end - 1`` lands."""
    n = len(sizes)
    if n == 0:
        return []
    buckets = max(1, min(int(buckets), n))
    total = sum(sizes) or 1
    target = total / float(buckets)
    ends: List[int] = []
    acc = 0
    for i, s in enumerate(sizes):
        acc += int(s)
        if i == n - 1 or acc >= target or acc > cap_bytes:
            ends.append(i + 1)
            acc = 0
    return ends
