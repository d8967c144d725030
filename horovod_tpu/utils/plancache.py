"""Persistent autotuned collective-plan cache with fleet-shared warm starts.

Rounds 9-13 built every ingredient of ROADMAP item 1 — the GP/EI
autotuner (``utils/autotune.py`` + ``core/src/parameter_manager.cc``),
per-(op, size_class) path telemetry (``mh_collective_seconds``,
``mh_collective_path_total``) — but every job still cold-started from
static defaults and one global ``HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD``.
This module closes the loop:

* **Plan model** — one *plan set* per topology fingerprint
  (``n_procs x local_chips x device_kind``): a per-``(op, size_class)``
  decision table (hier-vs-flat leg + cross-host codec engagement) and the
  tuned ``(fusion_threshold, cycle_time)`` operating point.  (Plans
  written when the flash kernels' blocks were tuned hold
  ``flash_blocks`` too: such a file loads, and the field is not read.)
* **Persistence** — a versioned on-disk blob under
  ``HOROVOD_PLAN_CACHE_DIR`` written with the spill-plane atomicity
  conventions (MAGIC + schema version + length + CRC32, same-directory
  temp + ``os.replace``).  Corrupt or version-mismatched blobs are
  skipped LOUDLY and the run falls back to defaults; ``hvd.init()``
  loads the blob so a rerun cold-starts at the tuned operating point.
* **Fleet sharing** — on worlds bootstrapped through the rendezvous KV,
  rank 0 publishes its loaded plan at init and every other member
  adopts the published copy, so late joiners and elastically respawned
  workers start from the pod's best-known plan instead of re-tuning —
  and so every member routes IDENTICALLY (divergent per-class routing
  would diverge the negotiated XLA programs).  Without a KV, the cache
  directory must be shared storage (like ``HOROVOD_STATE_SPILL_DIR``)
  or hold identical content on every host.
* **Tuning** — :func:`tune_collective_plans` is the SPMD sweep (every
  member calls it with identical arguments): per class, the GP/EI
  :class:`~.autotune.PlanTuner` proposes candidate plans, candidates are
  scored from the live ``mh_collective_seconds{op,size_class}`` telemetry
  the r11 metrics plane records, and scores are cross-rank averaged before
  every proposal/argmax so all members pin the same winner.

Env precedence: explicit gate envs (``HOROVOD_HIERARCHICAL_ALLREDUCE``
on/off or an explicit ``HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD``) win
over any plan AND suppress pinning; explicit ``HOROVOD_FUSION_THRESHOLD``
/ ``HOROVOD_CYCLE_TIME`` suppress the tuned-point warm start the same
way.
"""

from __future__ import annotations

import binascii
import json
import logging
import os
import re
import struct
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..common import metrics

LOG = logging.getLogger("horovod_tpu.plancache")

MAGIC = b"HVDPLAN1\n"
SCHEMA_VERSION = 1
_HEADER = struct.Struct("!IQI")  # schema_version, payload_len, crc32
_SUFFIX = ".plan"

# Fleet-shared KV key per topology fingerprint; the schema version is
# part of the key so a mixed-version fleet can never adopt a blob its
# decoder does not understand.
_KV_KEY = "plan/v%d/%s"


class PlanCacheInvalid(ValueError):
    """A plan blob failed validation (bad magic, torn payload, CRC
    mismatch, or schema-version mismatch)."""


def topology_fingerprint(n_procs: int, local_size: int,
                         device_kind: str) -> str:
    """Cache key for one payload-plane topology: plans tuned for a
    2-host x 4-chip v5e world must never warm-start an 8-host v4 one."""
    kind = re.sub(r"[^A-Za-z0-9]+", "_",
                  str(device_kind or "unknown")).strip("_")
    return "p%d-l%d-%s" % (int(n_procs), int(local_size),
                           kind or "unknown")


def empty_plan(fingerprint: str) -> dict:
    return {
        "schema": SCHEMA_VERSION,
        "fingerprint": fingerprint,
        # {"fusion_threshold": int, "cycle_time_ms": float,
        #  "converged": bool} once a tuner produced one.
        "tuned": None,
        # op -> {size_class(str) -> {"path": "hier"|"flat",
        #                            "codec": "none"|codec name}}
        "collectives": {},
    }


def _is_plan(obj) -> bool:
    return (isinstance(obj, dict) and obj.get("fingerprint")
            and isinstance(obj.get("collectives", {}), dict))


def plan_has_content(plan: Optional[dict]) -> bool:
    return bool(plan) and bool(plan.get("tuned")
                               or plan.get("collectives"))


# -- blob codec (spill-plane conventions) -----------------------------------

def encode(plan: dict) -> bytes:
    payload = json.dumps(plan, sort_keys=True).encode()
    return (MAGIC
            + _HEADER.pack(SCHEMA_VERSION, len(payload),
                           binascii.crc32(payload) & 0xFFFFFFFF)
            + payload)


def decode(blob: bytes) -> dict:
    """Validated plan dict or :class:`PlanCacheInvalid` — every header
    field is checked before the payload is trusted, and a schema bump
    invalidates old blobs instead of half-reading them."""
    head_len = len(MAGIC) + _HEADER.size
    if len(blob) < head_len or not blob.startswith(MAGIC):
        raise PlanCacheInvalid("bad magic or truncated header "
                               "(%d bytes)" % len(blob))
    schema, payload_len, crc = _HEADER.unpack(blob[len(MAGIC):head_len])
    if schema != SCHEMA_VERSION:
        raise PlanCacheInvalid(
            "plan schema v%d does not match this build's v%d; "
            "re-tune rather than misread" % (schema, SCHEMA_VERSION))
    payload = blob[head_len:]
    if len(payload) != payload_len:
        raise PlanCacheInvalid(
            "torn payload: header promises %d bytes, blob holds %d"
            % (payload_len, len(payload)))
    if binascii.crc32(payload) & 0xFFFFFFFF != crc:
        raise PlanCacheInvalid("payload CRC mismatch")
    try:
        plan = json.loads(payload.decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as exc:
        raise PlanCacheInvalid("undecodable payload: %s" % exc)
    if not _is_plan(plan):
        raise PlanCacheInvalid("payload is not a plan set")
    return plan


def plan_path(d: str, fingerprint: str) -> str:
    return os.path.join(d, "plan-%s%s" % (fingerprint, _SUFFIX))


def store(plan: dict, d: str) -> Optional[str]:
    """Persist one plan set atomically (same-directory temp +
    ``os.replace``, the spill convention — concurrent writers each
    land a complete blob, last one wins).  Never raises: a full disk
    degrades warm starts, it must not kill shutdown or tuning."""
    path = plan_path(d, plan["fingerprint"])
    try:
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=".tmp-plan-", dir=d)
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(encode(plan))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return path
    except OSError as exc:
        LOG.warning("plan-cache write to %s failed (%s); continuing "
                    "without a persisted plan", path, exc)
        return None


def load(d: str, fingerprint: str) -> Optional[dict]:
    """The persisted plan for this fingerprint, or None.  Bumps
    ``plan_cache_hits_total`` / ``plan_cache_misses_total``; corrupt or
    version-mismatched blobs are a LOUD miss (warning + defaults), so
    a bad blob can never silently pin wrong plans."""
    path = plan_path(d, fingerprint)
    try:
        with open(path, "rb") as f:
            plan = decode(f.read())
    except FileNotFoundError:
        metrics.counter("plan_cache_misses_total").inc()
        return None
    except (OSError, PlanCacheInvalid) as exc:
        metrics.counter("plan_cache_misses_total").inc()
        metrics.event("plan_cache_invalid", path=path, error=str(exc))
        LOG.warning("ignoring unusable plan cache %s (%s); falling "
                    "back to default plans", path, exc)
        return None
    if plan["fingerprint"] != fingerprint:
        metrics.counter("plan_cache_misses_total").inc()
        LOG.warning("plan cache %s claims fingerprint %s, expected %s; "
                    "falling back to default plans", path,
                    plan["fingerprint"], fingerprint)
        return None
    metrics.counter("plan_cache_hits_total").inc()
    return plan


# -- fleet sharing over the rendezvous KV -----------------------------------

def publish_kv(client, plan: dict):
    """Publish one plan set through the rendezvous KV (rank 0 at init,
    and again after a tuning sweep pins new winners) so late joiners
    and respawned workers adopt the pod's best-known plan.  Best
    effort: a dead KV degrades sharing, never the run."""
    try:
        client.put_json(_KV_KEY % (SCHEMA_VERSION, plan["fingerprint"]),
                        plan)
    except Exception as exc:  # noqa: BLE001 - warm starts are optional
        LOG.warning("plan KV publish failed (%s); members fall back to "
                    "their local caches", exc)


def adopt_kv(client, fingerprint: str,
             timeout: float = 60.0) -> Optional[dict]:
    """Block for rank 0's published plan (it publishes before its first
    collective, like the address table) and return it — adopting the
    SAME plan on every member is what keeps per-class routing
    SPMD-identical.  Returns None (loudly) on timeout or a torn
    record: the member then routes by defaults, matching what rank 0
    publishes when it has no plan."""
    try:
        raw = client.get_blocking(
            _KV_KEY % (SCHEMA_VERSION, fingerprint), timeout=timeout)
        plan = json.loads(raw)
        if not _is_plan(plan) or plan["fingerprint"] != fingerprint:
            raise ValueError("published blob is not a plan for %s"
                             % fingerprint)
        return plan
    except Exception as exc:  # noqa: BLE001 - degrade to defaults
        LOG.warning("plan KV adopt for %s failed (%s); using default "
                    "plans", fingerprint, exc)
        return None


# -- per-(op, size_class) routing controller --------------------------------

class PlanController:
    """Deterministic per-``(op, size_class)`` routing decisions for one
    topology fingerprint.

    Precedence per class: env pins (explicit hier mode/threshold —
    suppress everything, the r9 convention) > probe override (the
    tuning sweep forcing a candidate) > plans pinned this run > the
    loaded cache/KV plan > the default byte-threshold gate.  Every
    resolution path is a pure function of negotiated values and
    plan state that is identical on every member by construction
    (shared cache blob or KV adoption), so all members compile the
    same collective programs.
    """

    def __init__(self, fingerprint: str, plan: Optional[dict],
                 source: Optional[str], codec_name: str,
                 hier_available: bool, env_pinned: bool):
        self._lock = threading.Lock()
        self.fingerprint = fingerprint
        self.source = source or "cache"
        self.codec_name = (codec_name or "none")
        self.hier_available = bool(hier_available)
        self.env_pinned = bool(env_pinned)
        self._cached: Dict[Tuple[str, str], dict] = {}
        for op, classes in (plan or {}).get("collectives", {}).items():
            for cls, entry in classes.items():
                if isinstance(entry, dict) and "path" in entry:
                    self._cached[(op, str(cls))] = {
                        "path": entry["path"],
                        "codec": entry.get("codec", "none")}
        self._pinned: Dict[Tuple[str, str], dict] = {}
        self._seen: Dict[Tuple[str, str], dict] = {}
        self._counted: set = set()
        self._forced: Optional[dict] = None
        self._last_cls: Dict[str, str] = {}
        # Resolved-route memo for the dispatch hot path: (op, cls,
        # default_hier) -> (hier, codec_on).  default_hier is part of
        # the key because an unplanned class falls back to the byte
        # gate, and a non-pow2 threshold can split one pow2 class.
        # Invalidated by pin(); force() bypasses it entirely.
        self._memo: Dict[Tuple[str, str, bool], Tuple[bool, bool]] = {}

    def route(self, op: str, cls: str,
              default_hier: bool) -> Tuple[bool, bool]:
        """(use_hier, engage_codec) for one dispatch.  ``default_hier``
        is the global gate's answer; ``engage_codec`` True leaves the
        codec decision to the dtype/op-aware ``_wire_codec`` check."""
        if self._forced is None:
            # Lock-free fast path: per-(op, cls) resolution is
            # deterministic once counted, so repeat dispatches skip
            # the lock and the bookkeeping churn entirely.
            hit = self._memo.get((op, cls, bool(default_hier)))
            if hit is not None:
                return hit
        with self._lock:
            self._last_cls[op] = cls
            if self._forced is not None:
                e = self._forced
                return (e.get("path") == "hier" and self.hier_available,
                        e.get("codec", "none") not in ("", "none"))
            entry = None
            source = "default"
            if not self.env_pinned:
                entry = self._pinned.get((op, cls))
                if entry is not None:
                    source = "tuned"
                else:
                    entry = self._cached.get((op, cls))
                    if entry is not None:
                        source = self.source
            if entry is None:
                hier = bool(default_hier)
                codec_on = True
                codec = (self.codec_name
                         if hier and self.codec_name != "none"
                         else "none")
            else:
                hier = (entry.get("path") == "hier"
                        and self.hier_available)
                codec = entry.get("codec", "none")
                codec_on = (codec not in ("", "none")
                            and codec == self.codec_name)
            key = (op, cls)
            if (key, source) not in self._counted:
                self._counted.add((key, source))
                metrics.counter("plan_apply_total", source=source).inc()
            self._seen[key] = {"path": "hier" if hier else "flat",
                               "codec": codec if hier else "none",
                               "source": source}
            self._memo[(op, cls, bool(default_hier))] = (hier, codec_on)
            return hier, codec_on

    def force(self, entry: Optional[dict]):
        """Probe override: route EVERY class by ``entry`` until cleared
        (the tuning sweep brackets its timed collectives with this; all
        members force the same candidate at the same point, so the
        override is SPMD-consistent)."""
        with self._lock:
            self._forced = dict(entry) if entry is not None else None

    def last_class(self, op: str) -> Optional[str]:
        """The size class the newest ``route()`` call for ``op``
        resolved — how the sweep learns which class its fixed-size
        probe payload actually lands in (gate bytes are op-specific)."""
        with self._lock:
            return self._last_cls.get(op)

    def invalidate(self, op: str, cls: str) -> bool:
        """Drop one class's plan entry (the staleness verdict's
        actuation): cached and pinned entries both go, the route memo
        clears, and the class's counted/seen marks reset so the next
        dispatch re-resolves from scratch (by the default gate, counted
        as a fresh ``plan_apply_total{source="default"}`` — provenance
        stays honest about the fallback).  Returns whether any entry
        was actually dropped.  SPMD contract: called on every member at
        the same point (``check_plan_staleness`` routes the verdict
        through the rendezvous KV), never from rank-local judgement."""
        key = (op, str(cls))
        with self._lock:
            had = self._cached.pop(key, None) is not None
            had = (self._pinned.pop(key, None) is not None) or had
            self._seen.pop(key, None)
            self._counted = {k for k in self._counted if k[0] != key}
            self._memo.clear()  # the drop changes future resolutions
        if had:
            # A stale plan verdict also invalidates any frozen
            # negotiated schedule built over it (SPMD-safe: this runs
            # on every member at the same point, per the contract
            # above).  Lazy import — plancache must not pull the ops
            # package at module load.
            from ..ops import fastpath
            fastpath.thaw_all(
                "staleness",
                detail="plan %s/%s invalidated by staleness verdict"
                % (op, cls))
        return had

    def pin(self, op: str, cls: str, entry: dict) -> bool:
        """Pin a tuned winner for one class; refused (False) when env
        pins suppress planning — an explicit operator A/B must stay
        exactly what was asked for."""
        if self.env_pinned:
            return False
        with self._lock:
            self._pinned[(op, str(cls))] = dict(entry)
            self._memo.clear()  # the pin changes future resolutions
        return True

    def decisions(self) -> Dict[str, Dict[str, dict]]:
        """The live per-class decision table (bench ``levers.plan``)."""
        with self._lock:
            out: Dict[str, Dict[str, dict]] = {}
            for (op, cls), entry in sorted(self._seen.items()):
                out.setdefault(op, {})[cls] = dict(entry)
            return out

    def export_collectives(self) -> Dict[str, Dict[str, dict]]:
        """Decisions worth persisting: everything routed this run plus
        every pin, path/codec only (sources are runtime provenance)."""
        with self._lock:
            merged = dict(self._seen)
            for key, entry in self._pinned.items():
                merged[key] = {"path": entry.get("path", "flat"),
                               "codec": entry.get("codec", "none")}
            out: Dict[str, Dict[str, dict]] = {}
            for (op, cls), entry in sorted(merged.items()):
                out.setdefault(op, {})[cls] = {
                    "path": entry.get("path", "flat"),
                    "codec": entry.get("codec", "none")}
            return out


# -- process-wide plane state -----------------------------------------------

class _PlanPlane:
    def __init__(self):
        self.lock = threading.Lock()
        self.enabled = False
        self.tune_enabled = False
        self.dir: Optional[str] = None
        self.fingerprint: Optional[str] = None
        self.loaded: Optional[dict] = None
        self.source: Optional[str] = None  # "cache" | "kv"
        self.controller: Optional[PlanController] = None
        self.tuned_runtime: Optional[dict] = None
        self.kv = None  # live RendezvousClient for republish, or None
        self.rank: Optional[int] = None
        self.size: Optional[int] = None
        # Staleness-check state (lazy: built at the first
        # check_plan_staleness call so the ratio env is read when the
        # check runs, not at plane reset).
        self.staleness = None


_plane = _PlanPlane()


def reset():
    """Drop all plane state (tests, and re-init after shutdown)."""
    global _plane
    _plane = _PlanPlane()
    # The resilience plane rides the same world identity (rank / KV /
    # fingerprint); a plane reset means that identity is gone, so its
    # demotion state and SPMD check sequence must restart with it.
    from ..common import resilience
    resilience.reset()


def world_plane() -> _PlanPlane:
    """The live plan plane: world identity (rank, size, fingerprint),
    the rendezvous KV handle, and the active :class:`PlanController`.
    The data-plane resilience layer (common/resilience.py) reads this
    to publish/adopt SPMD-uniform degraded-route verdicts through the
    same KV record protocol as plan staleness."""
    return _plane


def _env_pins_gate() -> bool:
    """Whether explicit gate envs suppress per-class planning: an
    explicit hier mode (on/off — not the 'auto' default) or an
    explicit threshold means the operator chose the gate."""
    from ..common.config import env_explicit
    v = (os.environ.get("HVD_TPU_HIERARCHICAL_ALLREDUCE")
         or os.environ.get("HOROVOD_HIERARCHICAL_ALLREDUCE") or "")
    explicit_mode = v.strip().lower() not in ("", "auto")
    return explicit_mode or env_explicit(
        "HIERARCHICAL_ALLREDUCE_THRESHOLD")


def _agree_plan(plane, config, mode, n_procs, kv_world,
                local_plan):  # graftlint: spmd-uniform -- rank-0-publish -> blocking-adopt: rank 0's answer (its local blob, or the KV's prior one) is published under the fingerprint key; every other member blocks on that key and REPLACES its local view with the adopted answer or raises (multihost), so all members leave with the identical plan.  A KV-less multihost world drops the local blob entirely (per-host cache files may differ).
    """World agreement on the active plan.

    The local cache blob is a per-host filesystem read — two hosts can
    legitimately hold different blobs (independent disks, one stale
    rerun) — so it must never steer routing directly on a multi-member
    world.  Rank 0's view becomes THE plan by publishing it to the
    rendezvous KV; members adopt that published answer (blocking) or
    fail loudly.  Without a KV to agree through, a multihost world
    gets no plan at all: divergent per-class hier/flat choices compile
    divergent XLA programs — a distributed hang, not a slowdown (the
    r14 bug class).  tcp mode has no routing controller, so it keeps
    its local view (fusion/cycle pacing only, composition is
    negotiated per cycle).
    """
    plan = local_plan
    if kv_world:
        from ..runner.http_client import RendezvousClient
        plane.kv = RendezvousClient(config.rendezvous_addr,
                                    secret=config.secret_key)
        if plane.rank == 0:
            if plan is None:
                # A long-lived KV may still hold the plan the LAST run
                # republished at shutdown (the KV-only persistence
                # path, and dir-miss reruns against a shared
                # rendezvous): adopt it instead of clobbering it with
                # an empty answer — cross-run KV warm starts depend on
                # it, and it keeps rank 0's publish idempotent, so a
                # member racing the overwrite still reads identical
                # content.
                try:
                    prior = plane.kv.get_json(
                        _KV_KEY % (SCHEMA_VERSION, plane.fingerprint))
                except Exception:  # noqa: BLE001 - optional warm start
                    prior = None
                if (_is_plan(prior)
                        and prior["fingerprint"] == plane.fingerprint
                        and plan_has_content(prior)):
                    plan = prior
                    plane.source = "kv"
            # Publish even an empty plan: members block on this key,
            # and "no plan" is an answer they must agree on.
            publish_kv(plane.kv,
                       plan if plan is not None
                       else empty_plan(plane.fingerprint))
        else:
            adopted = adopt_kv(plane.kv, plane.fingerprint)
            if adopted is None and mode == "multihost":
                # A member that cannot learn rank 0's answer must NOT
                # guess: divergent per-class hier/flat choices diverge
                # the negotiated XLA programs across the world (a hang,
                # not a slowdown).  tcp mode has no routing controller,
                # so it degrades to its local view instead.
                raise RuntimeError(
                    "collective-plan KV adoption failed on a multihost "
                    "world: members must route by rank 0's published "
                    "plan or not at all; fix the rendezvous KV or "
                    "disable the plane with HOROVOD_PLAN_CACHE=0")
            if adopted is not None:
                # The adopted answer REPLACES any local view, even
                # when empty: agreeing on "no plan" beats routing by a
                # local blob rank 0 never saw.
                plan = adopted
                plane.source = ("kv" if plan_has_content(adopted)
                                else None)
    elif mode == "multihost" and n_procs > 1 and plan is not None:
        # No KV to agree through: members CANNOT verify their local
        # blobs match, and applying them anyway is precisely the
        # divergent-routing hang spmd-uniform exists to ban.  Drop the
        # blob (the run degrades to threshold routing and static
        # fusion defaults, still identical everywhere) and say why.
        LOG.warning(
            "plan cache: multihost world with no rendezvous KV — "
            "dropping the local plan blob (%s); per-host cache files "
            "cannot be proven identical, and divergent routing hangs "
            "the world.  Set HOROVOD_RENDEZVOUS_ADDR to share plans.",
            plane.dir)
        plan = None
        plane.source = None
    return plan


def bootstrap(config, topology, mode: str) -> Optional[dict]:
    """Load-and-apply at ``hvd.init()``: resolve the topology
    fingerprint, load the local cache (rank 0) or adopt rank 0's
    KV-published copy (other members — identical routing everywhere),
    warm-start the fusion/cycle tuner, seed the flash registry, and
    install the per-class routing controller (multihost mode).
    Returns the active plan (may be empty) or None when disabled."""
    plane = _plane
    plane.rank = topology.rank if topology is not None else None
    plane.size = topology.size if topology is not None else 1
    plane.enabled = bool(getattr(config, "plan_cache", True))
    plane.tune_enabled = (config.plan_autotune
                          if getattr(config, "plan_autotune", None)
                          is not None else bool(config.autotune))
    plane.dir = getattr(config, "plan_cache_dir", None)
    if not plane.enabled:
        return None
    n_procs = topology.size if topology is not None else 1
    # KV-only operation (ephemeral-disk pods): with no cache dir the
    # rendezvous KV still carries fleet sharing — rank 0 republishes
    # its live-tuned plan at shutdown, so respawned workers and the
    # next KV-bootstrapped run adopt it.  With neither dir nor KV
    # there is nothing to load or share: the plane is inert.
    kv_world = (mode in ("tcp", "multihost") and config.rendezvous_addr
                and n_procs > 1)
    if not plane.dir and not kv_world:
        plane.enabled = False
        return None
    local = 1
    kind = "host"
    if mode in ("inprocess", "multihost"):
        try:
            import jax
            devs = jax.local_devices()
            kind = getattr(devs[0], "device_kind", devs[0].platform)
            if mode == "multihost":
                local = len(devs)
        except Exception:  # noqa: BLE001 - fingerprint must not kill init
            pass
    plane.fingerprint = topology_fingerprint(n_procs, local, kind)

    local_plan = (load(plane.dir, plane.fingerprint)
                  if plane.dir else None)
    plane.source = "cache" if local_plan is not None else None
    plan = _agree_plan(plane, config, mode, n_procs, kv_world,
                       local_plan)
    plane.loaded = plan
    if plan is None:
        plan = empty_plan(plane.fingerprint)

    # Tuned (fusion, cycle) warm start: the cached operating point wins
    # over the static defaults but never over explicit operator envs.
    from ..common.config import env_explicit
    tuned = plan.get("tuned")
    if (tuned and not env_explicit("FUSION_THRESHOLD")
            and not env_explicit("CYCLE_TIME")):
        config.fusion_threshold_bytes = int(tuned["fusion_threshold"])
        config.cycle_time_ms = float(tuned["cycle_time_ms"])
        metrics.counter("plan_apply_total",
                        source=plane.source or "cache").inc()

    if mode == "multihost":
        plane.controller = PlanController(
            plane.fingerprint, plan, plane.source,
            config.cross_host_compression,
            hier_available=(config.hierarchical_allreduce != "off"),
            env_pinned=_env_pins_gate())
    return plan


def tuned_warm_start() -> Optional[Tuple[int, float, bool]]:
    """The loaded plan's (fusion_threshold, cycle_time_ms, converged)
    for tuner warm starts, or None when there is no plan — or when
    explicit operator envs pin the operating point (env wins and
    suppresses the warm start, the r9 precedence rule)."""
    plane = _plane
    plan = plane.loaded
    if not plane.enabled or not plan or not plan.get("tuned"):
        return None
    from ..common.config import env_explicit
    if env_explicit("FUSION_THRESHOLD") or env_explicit("CYCLE_TIME"):
        return None
    t = plan["tuned"]
    return (int(t["fusion_threshold"]), float(t["cycle_time_ms"]),
            bool(t.get("converged", False)))


def controller_for(n_procs: int, local_size: int,
                   device_kind: str) -> Optional[PlanController]:
    """The installed controller, iff its fingerprint matches this
    mesh's topology (process-set sub-meshes with other shapes must
    route by the default gate — their classes were never tuned)."""
    ctl = _plane.controller
    if ctl is None:
        return None
    fp = topology_fingerprint(n_procs, local_size, device_kind)
    if fp != ctl.fingerprint:
        return None
    # The controller's hier availability is refined by the REAL mesh:
    # a single-local-chip world can never route hier whatever the
    # plan says (deterministic on every member — k is a world
    # property).
    if local_size <= 1:
        ctl.hier_available = False
    return ctl


def note_tuned(fusion_threshold: int, cycle_time_ms: float,
               converged: bool):
    """Stage a live-tuned (fusion, cycle) operating point for
    persistence (the in-process engine calls this when its GP tuner
    converges; the native core's point is read at shutdown)."""
    plane = _plane
    with plane.lock:
        first = plane.tuned_runtime is None
        plane.tuned_runtime = {
            "fusion_threshold": int(fusion_threshold),
            "cycle_time_ms": float(cycle_time_ms),
            "converged": bool(converged)}
    if first:
        metrics.counter("plan_apply_total", source="tuned").inc()


def _merged_plan() -> Optional[dict]:
    plane = _plane
    if not plane.enabled or plane.fingerprint is None:
        return None
    plan = (dict(plane.loaded) if plane.loaded is not None
            else empty_plan(plane.fingerprint))
    plan["schema"] = SCHEMA_VERSION
    plan["fingerprint"] = plane.fingerprint
    with plane.lock:
        if plane.tuned_runtime is not None:
            plan["tuned"] = dict(plane.tuned_runtime)
    if plane.controller is not None:
        merged = dict(plan.get("collectives", {}))
        for op, classes in plane.controller.export_collectives().items():
            dst = dict(merged.get(op, {}))
            dst.update(classes)
            merged[op] = dst
        plan["collectives"] = merged
    return plan


def persist(publish: bool = True) -> Optional[str]:
    """Write the merged plan to the cache (rank 0 or rankless worlds;
    every writer lands an atomic complete blob anyway) and republish
    it to the KV so live members' successors warm-start from it."""
    plane = _plane
    plan = _merged_plan()
    if plan is None or not plan_has_content(plan):
        return None
    path = None
    if plane.rank in (None, 0) and plane.dir:
        path = store(plan, plane.dir)
    if publish and plane.kv is not None and plane.rank in (None, 0):
        publish_kv(plane.kv, plan)  # graftlint: spmd-uniform -- rank-0-only republish: this blob is the NEXT run's adoption point (never read back into this run's routing); members hit the rank guard above
    return path


def finalize(tcp_core=None, engine=None):
    """Shutdown hook: harvest the live tuners' operating points (the
    native core's autotune state, or the in-process ParameterManager)
    and persist the merged plan.  Never raises into shutdown."""
    plane = _plane
    if not plane.enabled:
        return
    try:
        # samples > 0 distinguishes "tuned THIS run" from a frozen
        # warm start replaying the cached point: only live tuning is
        # (re)staged, so plan_apply_total{source="tuned"} stays honest
        # provenance and a pure warm-start run re-persists the loaded
        # plan unchanged through the merge.
        pm = getattr(engine, "parameter_manager", None)
        if pm is not None and pm.samples_done > 0:
            note_tuned(pm.fusion_threshold, pm.cycle_time_ms, pm.frozen)
        if tcp_core is not None:
            st = tcp_core.autotune_state()
            if st["samples"] > 0:
                note_tuned(st["fusion_threshold"], st["cycle_time_ms"],
                           bool(st["converged"]))
        persist()
    except Exception as exc:  # noqa: BLE001 - shutdown must not fail
        LOG.warning("plan-cache finalize failed: %s", exc)


def describe() -> dict:
    """Attribution block for ``bench.py``'s ``levers.plan``: cache
    path, hit/miss counters, schema version, plan source and the
    per-class decision table."""
    plane = _plane
    out = {
        "enabled": plane.enabled,
        "schema": SCHEMA_VERSION,
        "dir": plane.dir,
        "fingerprint": plane.fingerprint,
        "source": plane.source,
        "hits": metrics.series_sum("plan_cache_hits_total"),
        "misses": metrics.series_sum("plan_cache_misses_total"),
        "apply": {
            src: metrics.series_sum("plan_apply_total", source=src)
            for src in ("cache", "kv", "tuned", "default")},
        "tune_samples": metrics.series_sum("plan_tune_samples_total"),
    }
    if plane.controller is not None:
        out["decisions"] = plane.controller.decisions()
    with plane.lock:
        if plane.tuned_runtime is not None:
            out["tuned"] = dict(plane.tuned_runtime)
    return out


# -- plan staleness: observed-vs-expected drift, SPMD-uniform ---------------

# One record per fingerprint on the rendezvous KV: rank 0 overwrites it
# every check with {"seq": N, "stale": [trip history]}; members gate on
# seq and apply trips by their ``apply_at`` seq — never on local
# judgement.
_STALE_KEY = "plan/stale/v%d/%s"


class _StalenessState:
    def __init__(self):
        from ..common import skew
        self.seq = 0                 # checks this process has run
        self.tracker = skew.ClassLatencyTracker()  # rank 0 only
        self.entries: List[dict] = []  # rank 0's trip history
        self.applied = 0             # trips applied locally
        self.rearmed: List[Tuple[str, str]] = []  # awaiting re-tune
        self.warned_no_kv = False


def _staleness_state() -> _StalenessState:
    plane = _plane
    with plane.lock:
        if plane.staleness is None:
            plane.staleness = _StalenessState()
        return plane.staleness


def retune_pending() -> List[Tuple[str, str]]:
    """Classes whose cached plan entry went stale and now await
    re-tuning — appended exactly once per trip by
    :func:`check_plan_staleness`, consumed by the caller that re-runs
    :func:`tune_collective_plans` for them (every member sees the
    identical list: trips only ever arrive through the KV verdict)."""
    st = _plane.staleness
    return list(st.rearmed) if st is not None else []


def consume_retune() -> List[Tuple[str, str]]:
    """Pop the pending re-tune classes (call right before sweeping
    them, on every member — the SPMD calling contract)."""
    st = _plane.staleness
    if st is None:
        return []
    out, st.rearmed = list(st.rearmed), []
    return out


def _apply_stale(plane, entry: dict):
    op, cls = entry["op"], entry["size_class"]
    if plane.controller is not None:
        plane.controller.invalidate(op, cls)
    metrics.counter("plan_staleness_total", op=op,
                    size_class=cls).inc()
    metrics.event("plan_stale", scope="member", rank=plane.rank,
                  **entry)
    st = plane.staleness
    st.rearmed.append((op, cls))
    LOG.warning(
        "plan entry (%s, %s) invalidated as STALE (observed %.6fs vs "
        "baseline %.6fs, %.1fx drift): routing falls back to the "
        "default gate and the class is re-armed for tuning",
        op, cls, entry.get("observed_s", 0.0),
        entry.get("baseline_s", 0.0), entry.get("ratio", 0.0))


def check_plan_staleness(timeout: float = 60.0) -> Optional[dict]:  # graftlint: spmd-uniform -- rank-0-decide -> KV-adopt: only rank 0's ClassLatencyTracker ever produces a trip; the trip history is published under the fingerprint key with an apply_at seq, every member blocks for a record covering ITS OWN seq and applies exactly the trips with apply_at <= that seq, so all members invalidate the same classes at the same check index (in between, routing is untouched everywhere).  KV-less multi-member worlds return None before any state mutates.
    """Observed-vs-expected plan drift check — the decide half of the
    staleness loop.  EVERY member calls this at the same point in its
    step sequence (the ``tune_collective_plans`` SPMD contract; pick a
    cadence you can afford — each check is one KV round-trip).

    Rank 0 feeds its live ``mh_collective_seconds`` per-class totals
    into a :class:`~horovod_tpu.common.skew.ClassLatencyTracker`:
    a class whose window mean drifts past
    ``HOROVOD_PLAN_STALENESS_RATIO`` x its recorded baseline (the
    latency the active plan delivered when tracking began) is STALE —
    one class per check, worst first.  The verdict is routed through
    the rendezvous KV (rank 0 publishes its trip history stamped with
    the check seq; members block for a record covering their own seq)
    so the invalidation lands at the SAME check index on every member
    — per-class routing must never diverge (the r14 hang class).  On
    a trip every member drops the class from its controller
    (:meth:`PlanController.invalidate`), bumps
    ``plan_staleness_total{op,size_class}``, journals ``plan_stale``,
    and re-arms the class for tuning exactly once
    (:func:`retune_pending`).

    Returns the trip applied this check (or None).  Multi-member
    worlds without a rendezvous KV cannot agree and observe nothing
    (warned once); a member that cannot reach rank 0's record raises
    rather than guess."""
    plane = _plane
    if not plane.enabled or plane.fingerprint is None:
        return None
    from ..common import skew
    if skew.plan_staleness_ratio() <= 0:
        return None
    st = _staleness_state()
    multi = (plane.size or 1) > 1
    if multi and plane.kv is None:
        if not st.warned_no_kv:
            st.warned_no_kv = True
            LOG.warning(
                "plan staleness check skipped: multi-member world "
                "with no rendezvous KV to agree through (set "
                "HOROVOD_RENDEZVOUS_ADDR) — rank-local invalidation "
                "would diverge per-class routing")
        return None
    st.seq += 1
    key = _STALE_KEY % (SCHEMA_VERSION, plane.fingerprint)
    if plane.rank in (None, 0):
        verdict = st.tracker.update(
            skew._class_totals(metrics.snapshot()))
        if verdict is not None:
            st.entries.append(dict(verdict, apply_at=st.seq))
        if multi:
            plane.kv.put_json(key, {"seq": st.seq,
                                    "stale": st.entries})
        visible = st.entries
    else:
        deadline = time.monotonic() + timeout
        rec = None
        while True:
            rec = plane.kv.get_json(key)
            if isinstance(rec, dict) and rec.get("seq", 0) >= st.seq:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "plan staleness check: rank 0 never published "
                    "check #%d for %s — members must adopt rank 0's "
                    "verdict or not at all (the divergent-routing "
                    "hang class)" % (st.seq, plane.fingerprint))
            time.sleep(0.05)
        # Only trips rank 0 decided AT OR BEFORE this member's own
        # check index apply now; later ones apply at their own index.
        visible = [e for e in rec.get("stale", ())
                   if e.get("apply_at", 0) <= st.seq]
    fresh = visible[st.applied:]
    for entry in fresh:
        _apply_stale(plane, entry)
    st.applied = len(visible)
    return dict(fresh[-1]) if fresh else None


# -- the tuning sweep -------------------------------------------------------

def _hist_totals(name: str, **labels) -> Tuple[float, float]:
    """(sum_seconds, count) over every series of one histogram family
    whose labels contain ``labels`` — the live-telemetry read the
    sweep scores from."""
    fam = metrics.snapshot().get(name)
    total, count = 0.0, 0.0
    if not fam:
        return total, count
    for row in fam.get("series", []):
        row_labels = row.get("labels", {})
        if all(row_labels.get(k) == str(v) for k, v in labels.items()):
            total += float(row.get("sum", 0.0))
            count += float(row.get("count", 0.0))
    return total, count


def _probe_payload(op: str, nbytes: int, size: int):
    import numpy as np
    n = max(int(nbytes) // 4, size)
    if op == "alltoall":
        n = -(-n // size) * size  # uniform splits need dim0 % size == 0
    # Rank-identical payloads: the probe measures movement, and
    # identical inputs keep every reduce numerically boring.
    return np.random.RandomState(0).randn(n).astype(np.float32)


def _op_runner(op: str, hvd):
    if op == "allreduce":
        return lambda p: hvd.allreduce(p, op=hvd.Sum,
                                       name="plan.probe.allreduce")
    if op == "allgather":
        return lambda p: hvd.allgather(p, name="plan.probe.allgather")
    if op == "broadcast":
        return lambda p: hvd.broadcast(p, root_rank=0,
                                       name="plan.probe.broadcast")
    if op == "reducescatter":
        return lambda p: hvd.reducescatter(p,
                                           name="plan.probe.reducescatter")
    if op == "alltoall":
        return lambda p: hvd.alltoall(p, name="plan.probe.alltoall")
    raise ValueError("unknown probe op %r" % op)


def tune_collective_plans(sizes_bytes=(1 << 20,), ops=("allreduce",),
                          iters: int = 3, samples_per_class: int = 0,
                          pin: bool = True, persist_after: bool = True):
    """SPMD per-(op, size_class) plan sweep over the widened search
    space: hier-vs-flat leg x cross-host codec engagement.

    EVERY member process must call this with identical arguments: the
    sweep forces one candidate
    plan at a time, drives ``iters`` real collectives through the
    public eager API, scores the candidate from the live
    ``mh_collective_seconds{op,size_class}`` dispatch-to-completion
    telemetry (wall-clock fallback when the histogram window is
    racing), cross-rank AVERAGES every score before feeding the GP/EI
    :class:`~.autotune.PlanTuner` — so proposals and the final argmax
    are identical on all members — and pins each class's winner into
    the live routing plan (env gate pins suppress pinning).  Winners
    are persisted and republished so the whole fleet warm-starts.

    Returns ``{(op, size_class): {"best", "pinned", "samples",
    "scores"}}``.
    """
    import numpy as np

    import horovod_tpu as hvd  # lazy: this module is imported by init

    from .autotune import PlanTuner

    plane = _plane
    ctl = plane.controller
    if ctl is None:
        raise RuntimeError(
            "plan tuning needs the collective-plan plane: multihost "
            "mode with HOROVOD_PLAN_CACHE_DIR set (and HOROVOD_PLAN_CACHE "
            "not disabled)")
    if not plane.tune_enabled:
        raise RuntimeError(
            "plan tuning is disabled: set HOROVOD_PLAN_AUTOTUNE=1 "
            "(or HOROVOD_AUTOTUNE=1) to enable the per-class sweep")
    size = hvd.size()
    candidates: List[dict] = [{"path": "flat", "codec": "none"}]
    coords = [(0.0, 0.0)]
    if ctl.hier_available:
        candidates.append({"path": "hier", "codec": "none"})
        coords.append((1.0, 0.0))
        if ctl.codec_name != "none":
            candidates.append({"path": "hier", "codec": ctl.codec_name})
            coords.append((1.0, 1.0))

    def avg_scalar(x: float) -> float:  # graftlint: spmd-uniform -- cross-rank Average over the collective plane: every member contributes its local score and receives the identical mean, so GP proposals and the final argmax match on all members
        # Cross-rank mean via the regular collective plane: identical
        # inputs ordering -> bit-identical result on every member.
        v = np.asarray([x], np.float32)
        return float(np.asarray(hvd.allreduce(
            v, op=hvd.Average, name="plan.probe.score")).reshape(-1)[0])

    results = {}
    for op in ops:
        runner = _op_runner(op, hvd)
        for nbytes in sizes_bytes:
            payload = _probe_payload(op, int(nbytes), size)
            tuner = PlanTuner(coords,
                              max_samples=samples_per_class * len(coords)
                              or None)
            cls = None
            while not tuner.converged:
                idx = tuner.propose()
                ctl.force(candidates[idx])
                try:
                    s0, c0 = _hist_totals("mh_collective_seconds", op=op)
                    t0 = time.perf_counter()
                    for _ in range(max(int(iters), 1)):
                        runner(payload)
                    wall = time.perf_counter() - t0
                    s1, c1 = _hist_totals("mh_collective_seconds", op=op)
                finally:
                    ctl.force(None)
                cls = ctl.last_class(op) or "0"
                # Live-telemetry score (dispatch->completion from the
                # r11 histogram); the wall clock covers the race where
                # the last completion's observe lands after the read.
                secs = (s1 - s0) if (c1 - c0) >= iters else wall
                score = float(int(nbytes) * max(int(iters), 1)
                              / max(secs, 1e-9))
                tuner.record(idx, avg_scalar(score))
                metrics.counter("plan_tune_samples_total", op=op,
                                size_class=cls).inc()
            best_idx = tuner.best()
            entry = dict(candidates[best_idx])
            pinned = bool(pin) and ctl.pin(op, cls, entry)
            results[(op, cls)] = {
                "best": entry, "pinned": pinned,
                "samples": tuner.samples,
                "scores": tuner.mean_scores(),
            }
            if not pinned and pin:
                LOG.warning(
                    "plan pin for (%s, %s) suppressed: explicit "
                    "hierarchical gate env wins over the tuner", op, cls)
    if persist_after:
        persist()
    return results
