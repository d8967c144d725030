"""Unified metrics & structured-events plane: the process-local registry.

Rounds 6-10 built lint, fault-injection, self-healing and preemption
planes, but the only quantitative windows into a running world were the
Chrome-trace timeline and ad-hoc log lines.  This module is the
always-on substrate those planes (and the GP autotuner, and a fleet
operator's Prometheus) can actually consume:

* **Registry** — dependency-free, thread-safe, process-local counters,
  gauges and log2-bucket histograms, optionally labeled.  Every series
  name is declared exactly once in :data:`NAMES` (the one canonical
  table, enforced at runtime here and statically by the graftlint
  ``metric-*`` rules) so a typo can never fork a series.
* **Exposition** — ``render_prometheus()`` emits Prometheus text
  (served unauthenticated at ``GET /metrics`` on the rendezvous KV
  server: it is read-only operational telemetry, carries no payload
  data, and scrapers cannot compute the launcher HMAC);
  ``snapshot()`` returns the same model as a plain dict
  (``hvd.metrics_snapshot()``); ``render_merged()`` fuses the driver's
  and every worker's snapshots into one scrape with a ``rank`` label
  per source — the elastic driver's ``/metrics`` is fleet-wide.
* **Event journal** — ``event(kind, ...)`` appends one JSON line per
  structured event (drain, election, stall, fault fire, spill
  corruption) to ``HOROVOD_METRICS_DIR``: atomic ``O_APPEND`` writes,
  rank-stamped, per-process monotonic ``seq``, mirrored into the
  ``events_total`` counter.  Unset dir = counters only, no IO.
* **Host spans** — ``span(name)`` times a piece of the program's own
  work on the host: at once an observation of the one histogram family
  ``hvd_span_seconds{span}``, a record with a start, an end and a
  parent in a bounded in-memory list (``span_records()``,
  ``hvd.span_records()``), and a ``jax.profiler.TraceAnnotation``, so
  that under any profile the span lies on the profiler's host lines,
  on the device trace's clock.  The names are the host section of
  ``common/scopes.py``.

Label cardinality is bounded per family by
``HOROVOD_METRICS_MAX_SERIES`` (default 256): past the cap new label
combinations collapse into one ``overflow="true"`` series and bump
``metrics_dropped_series_total`` — a runaway label (a tensor name, a
group id) degrades resolution, never memory.  Group-id correlation
therefore rides the *timeline* (``args.group`` on EXEC events) and the
*journal*, while metric labels stay low-cardinality (op, size class,
path, site).

Nothing here may raise into an instrumented seam: journal IO failures
degrade to a warning, and the registry's own strictness (unknown or
kind-mismatched names raise) is aimed at authors, caught at first use
in any test that touches the seam.
"""

from __future__ import annotations

import collections
import functools
import itertools
import json
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from . import scopes
from .envutil import env_int

LOG = logging.getLogger("horovod_tpu.metrics")

# The canonical series table: every metric name in the tree, declared
# once with its kind and help string.  The graftlint ``metric-*`` rules
# cross-check every ``metrics.counter/gauge/histogram`` call site
# against this table (unregistered, kind-mismatched, duplicate and
# orphaned names are findings); docs/observability.md carries the same
# table for operators.
NAMES: Dict[str, Tuple[str, str]] = {
    # -- engine plane (the in-process CollectiveEngine and the
    #    multihost executor both report these; one process only ever
    #    runs one of them) --
    "engine_cycles_total": (
        "counter", "execution cycles (negotiated groups in multihost "
                   "mode) that dispatched at least one collective"),
    "engine_cycle_seconds": (
        "histogram", "wall time of one execution cycle"),
    "engine_queue_depth": (
        "gauge", "entries drained at the start of the latest cycle "
                 "(multihost: payloads parked awaiting negotiation)"),
    "engine_bytes_submitted_total": (
        "counter", "payload bytes enqueued into the engine"),
    "engine_bytes_fused_total": (
        "counter", "payload bytes that rode a multi-tensor fused "
                   "execution (vs dispatched alone)"),
    "engine_tensors_fused_total": (
        "counter", "tensors that rode multi-tensor fused executions"),
    "exec_cache_hits": (
        "gauge", "compiled-executable cache hits since process start"),
    "exec_cache_misses": (
        "gauge", "compiled-executable cache misses (compiles) since "
                 "process start"),
    "engine_last_group_id": (
        "gauge", "monotonic id of the newest dispatched collective "
                 "group; the same id tags the group's timeline EXEC "
                 "events (args.group) for cross-plane correlation"),
    # -- steady-state fast path (frozen negotiated schedules) --
    "fastpath_frozen_cycles_total": (
        "counter", "execution cycles dispatched straight off a frozen "
                   "negotiated schedule, skipping request "
                   "gather/fuse/broadcast (upstream response_cache.cc "
                   "parity); disjoint from engine_cycles_total so a "
                   "cached-schedule dispatch is never double-counted "
                   "as a negotiation cycle"),
    "fastpath_thaws_total": (
        "counter", "frozen schedules invalidated back to full "
                   "negotiation, labeled reason (shape|membership|"
                   "staleness|route|deadline); the paired "
                   "fastpath_thaw event carries the frozen schedule's "
                   "group id for timeline correlation"),
    "engine_overlap_bucket_seconds": (
        "histogram", "per-bucket wall time of a frozen fused cycle "
                     "(HOROVOD_OVERLAP_BUCKETS contiguous staging "
                     "buckets, each dispatched the instant its last "
                     "tensor lands): eager reports dispatch time, "
                     "multihost dispatch-to-completion"),
    # -- multihost payload plane --
    "mh_collective_seconds": (
        "histogram", "dispatch-to-completion latency of one negotiated "
                     "group, labeled op + pow2 size_class bytes"),
    "mh_bus_bytes_total": (
        "counter", "WIRE bytes submitted to the cross-host collective "
                   "(post-compression when a codec is active, payload "
                   "bytes otherwise), labeled op + path (hier|flat)"),
    "mh_collective_path_total": (
        "counter", "collective executions by op + path (hier|flat)"),
    "mh_compressed_collectives_total": (
        "counter", "cross-host collectives whose wire leg rode a "
                   "compression codec, labeled op + codec"),
    "mh_compression_ratio": (
        "gauge", "payload-to-wire byte ratio of the most recent "
                 "compressed cross-host collective, labeled op + "
                 "codec (4.0 = int8 from f32, incl. scale overhead)"),
    # -- self-healing data plane (common/resilience.py) --
    "mh_collective_failures_total": (
        "counter", "negotiated groups that error-completed, labeled "
                   "op + reason (deadline|transport|corrupt|error) — "
                   "the failure-side complement of "
                   "mh_collective_seconds, which only records clean "
                   "completions"),
    "mh_leg_retries_total": (
        "counter", "hier cross-host leg attempts repeated by the "
                   "data-plane guard (transient transport faults and "
                   "the single wire-integrity re-stage), labeled op + "
                   "size_class"),
    "mh_degraded_routes": (
        "gauge", "1 while an (op, size_class) hier route is demoted "
                 "to the flat plane after sustained leg failures, 0 "
                 "after the re-promotion probe clears it (rank-0 KV "
                 "verdict; every member reports its adopted view)"),
    "collective_deadline_expired_total": (
        "counter", "negotiated groups error-completed because they "
                   "outlived their per-collective deadline "
                   "(HOROVOD_COLLECTIVE_TIMEOUT_SECS + per-GiB "
                   "scaling), labeled op — each expiry poisons the "
                   "engine so elastic restores instead of hanging"),
    # -- collective-plan cache (persistent autotuned plans) --
    "plan_cache_hits_total": (
        "counter", "persisted collective-plan blobs successfully "
                   "loaded at init (topology-fingerprint match, valid "
                   "CRC and schema)"),
    "plan_cache_misses_total": (
        "counter", "plan-cache probes that found no usable blob "
                   "(absent, corrupt, schema- or fingerprint-"
                   "mismatched — the latter are warned about loudly)"),
    "plan_apply_total": (
        "counter", "plan decisions applied to live routing or tuner "
                   "warm starts, labeled source (cache|kv|tuned|"
                   "default); counted once per (op, size_class) "
                   "resolution, not per collective"),
    "plan_tune_samples_total": (
        "counter", "per-class plan-tuner samples scored by the GP/EI "
                   "sweep, labeled op + size_class (zero on a "
                   "warm-started rerun = the cache skipped re-tuning)"),
    # -- runner control plane (r8 retry/backoff layer) --
    "rpc_attempts_total": (
        "counter", "control-plane RPC attempts (including retries)"),
    "rpc_transient_failures_total": (
        "counter", "transient RPC failures absorbed by retry/backoff"),
    "rpc_giveups_total": (
        "counter", "retried RPCs that exhausted their retry budget or "
                   "deadline and escalated"),
    # -- HA control plane (journaled KV, warm-standby failover) --
    "control_leader_term": (
        "gauge", "this KV server's current leader term (fencing "
                 "epoch; followers report the leader term they track)"),
    "control_failovers_total": (
        "counter", "standby promotions after leader lease expiry"),
    "kv_journal_bytes_total": (
        "counter", "bytes appended to the control-plane write-ahead "
                   "journal"),
    "kv_journal_skipped_records_total": (
        "counter", "torn/corrupt journal records (or snapshots) "
                   "skipped during replay"),
    # -- elastic plane: driver side --
    "elastic_epoch": (
        "gauge", "current published world epoch (driver)"),
    "elastic_spawn_total": (
        "counter", "worker processes spawned (driver)"),
    "elastic_drain_total": (
        "counter", "workers that left via the drain protocol (planned "
                   "removal: preemption, stall abort)"),
    "elastic_worker_failures_total": (
        "counter", "worker processes reaped with a failure exit"),
    "elastic_blacklist_total": (
        "counter", "hosts blacklisted after crossing the failure "
                   "threshold"),
    # -- elastic plane: worker side --
    "elastic_elections_total": (
        "counter", "state-root elections this worker participated in"),
    "spill_commits_total": (
        "counter", "durable commit blobs spilled to "
                   "HOROVOD_STATE_SPILL_DIR"),
    "spill_commit_seconds": (
        "histogram", "wall time of one durable commit spill "
                     "(encode + write + fsync + rename + prune)"),
    "spill_crc_failures_total": (
        "counter", "spill/replica blobs rejected by CRC/length "
                   "validation (torn writes, bit flips)"),
    "shardspill_restore_bytes_total": (
        "counter", "bytes this process streamed from durable storage "
                   "during sharded-commit restore (the N→M resharding "
                   "claim: stays well under full-state size per host)"),
    "shardspill_shard_fallbacks_total": (
        "counter", "sharded-restore reads that fell back to a buddy "
                   "copy of the same shard after a corrupt first copy "
                   "(per-shard fallback, commit preserved)"),
    # -- multi-tenant pod scheduler --
    "tenant_slots": (
        "gauge", "pod-scheduler slot bookkeeping per tenant, labeled "
                 "tenant + state (allocated = slots currently assigned; "
                 "pending = shortfall below the tenant's min_np while "
                 "it waits for capacity)"),
    "tenant_preemptions_total": (
        "counter", "scheduler-initiated drain preemptions, labeled "
                   "tenant (planned removals via the r10 drain path — "
                   "never a blacklist entry or failure count)"),
    "tenant_wait_seconds": (
        "histogram", "time a tenant spent waiting for capacity, "
                     "labeled tenant: admission->first slots and "
                     "preemption->resume (the scheduler's fairness/"
                     "latency series)"),
    # -- serving plane (continuous-batching request router + replicas) --
    "serving_requests_total": (
        "counter", "inference requests by TERMINAL outcome, labeled "
                   "deployment + outcome (ok|deadline|dropped); a "
                   "requeued batch is not terminal — its requests "
                   "count exactly once, when they finally resolve"),
    "serving_batch_size": (
        "histogram", "requests coalesced into one dispatched batch "
                     "(the continuous-batching analog of tensor-fusion "
                     "efficiency)"),
    "serving_queue_depth": (
        "gauge", "requests queued and not yet dispatched, labeled "
                 "deployment (the autoscaler's primary input)"),
    "serving_request_seconds": (
        "histogram", "arrival-to-completion latency of one inference "
                     "request, labeled deployment (p50/p99 SLO series)"),
    # -- skew observatory (online straggler detection + plan staleness,
    #    common/skew.py; the elastic driver feeds it from the fleet
    #    /metrics pull and serves GET /skew from it) --
    "straggler_score": (
        "gauge", "per-rank arrival-lag skew vs the fleet median, "
                 "labeled rank (1.0 = at the median; in a synchronous "
                 "collective the straggler is the member everyone "
                 "waits FOR, so its own dispatch-to-completion is the "
                 "fleet minimum and its score = median/own spikes)"),
    "straggler_detections_total": (
        "counter", "sustained-skew straggler detections, labeled rank "
                   "+ action (observe|shrink|drain — the response the "
                   "observatory actually took)"),
    "plan_staleness_total": (
        "counter", "cached-plan entries declared STALE because the "
                   "observed per-class latency drifted past "
                   "HOROVOD_PLAN_STALENESS_RATIO x the recorded "
                   "baseline, labeled op + size_class (each trip "
                   "invalidates the class's routing entry and re-arms "
                   "the plan tuner exactly once)"),
    # -- kernels (ops/pallas_kernels.py, ops/ssd_kernels.py) --
    "hvd_flash_backward_calls_total": (
        "counter", "backward passes of flash_attention by the form they "
                   "took, labeled form (onepass|two_kernel|xla) + "
                   "window (0|1); counted as a call is traced, so once "
                   "for every time a layer scan or a recomputation "
                   "traces it and never again for a compiled step"),
    "hvd_flash_block_pairs_total": (
        "counter", "block pairs a flat head's grid visits in a flash "
                   "call, full or banded, labeled kernel "
                   "(fwd|dq|dkv|onepass) + kind (interior = seen whole, "
                   "no mask; masked = cut by the diagonal or a window's "
                   "far edge); the call's schedule, counted as its kernel "
                   "is traced, like hvd_flash_backward_calls_total"),
    "hvd_flash_shared_key_calls_total": (
        "counter", "flash kernels that read a shared key part (k_shared: "
                   "columns every head's key ends in, [B, S, d_s]) as an "
                   "operand of its own, labeled kernel "
                   "(fwd|dq|dkv|onepass); counted as the kernel is traced, "
                   "like hvd_flash_block_pairs_total, and absent where no "
                   "call hands the kernels such a part"),
    "hvd_ssd_scan_calls_total": (
        "counter", "state-space scans (models/state_space.py: "
                   "ssd_chunked) by the form their shapes took, labeled "
                   "form (kernel|xla); counted as a scan is traced, like "
                   "hvd_flash_backward_calls_total"),
    "hvd_delta_rule_calls_total": (
        "counter", "delta-rule cores (models/linear_attention.py: "
                   "kda_chunked) by the form their shapes took, labeled "
                   "form (kernel = the kernels for a decay for every "
                   "channel | head_kernel = those for a decay a head | "
                   "xla) + decay (channel = one for every key channel | "
                   "head = one a head); counted as a core is traced, like "
                   "hvd_flash_backward_calls_total"),
    "hvd_latent_attention_calls_total": (
        "counter", "latent-attention blocks (models/transformer.py: "
                   "_latent_attention_block) by the form their attention "
                   "took, labeled form (kernel = flash_attention at the "
                   "two head sizes | xla = local_attention); counted as a "
                   "block is traced, like hvd_flash_backward_calls_total"),
    "hvd_dense_ffn_calls_total": (
        "counter", "dense SwiGLU feed-forwards (models/transformer.py: "
                   "_dense_ffn) by their form, labeled form (split = every "
                   "operand of the products a buffer of its own: the "
                   "weights' casts, the input, silu(a) * g, (d_a, d_g) and "
                   "the incoming gradient made once); counted as a layer is traced, like "
                   "hvd_flash_backward_calls_total"),
    "hvd_indexer_scored_pairs_total": (
        "counter", "(query, key) pairs the lightning indexers of a step's "
                   "sparse-attention layers score (every causal pair of a "
                   "sequence; models/transformer.py: _count_sparse_pairs), "
                   "counted as a step is traced: once a traced step, "
                   "every layer of the scan with its own"),
    "hvd_sparse_attention_pairs_total": (
        "counter", "(query, key) pairs a step's sparse attention attends "
                   "over (a query's top_k kept keys, or every causal key it "
                   "has where it has fewer), counted as a step is traced, "
                   "like hvd_indexer_scored_pairs_total"),
    # -- lifecycle: the program's own set-up, timed on the host --
    "hvd_span_seconds": (
        "histogram", "wall time of one host span, labeled span (the host "
                     "section of common/scopes.py: hvd.init and its "
                     "children, hvd.mesh, hvd.build_state, "
                     "hvd.optimizer_init, hvd.broadcast, hvd.shard_batch, "
                     "hvd.import, and JAX's compile stages as "
                     "hvd.compile_trace|lower|backend|cache_read); the "
                     "same spans with start, end and parent are "
                     "hvd.span_records()"),
    "hvd_compile_programs_total": (
        "counter", "executables this process built or loaded since "
                   "place_compile_cache() first ran, labeled cache (hit = "
                   "read from the persistent compile cache, miss = "
                   "compiled); one a backend_compile_duration event of "
                   "JAX, so it equals the hvd.compile_backend records"),
    # -- cross-cutting --
    "stall_detected_total": (
        "counter", "stall-inspector warnings (a collective outlived "
                   "the warning threshold)"),
    "fault_injections_total": (
        "counter", "faultline site fires, labeled site + action "
                   "(injection certification reads this)"),
    "events_total": (
        "counter", "structured journal events emitted, labeled kind "
                   "(bumped even when no journal dir is set)"),
    "metrics_dropped_series_total": (
        "counter", "label combinations collapsed into the overflow "
                   "series by the cardinality guard"),
}

_KINDS = ("counter", "gauge", "histogram")

# Histogram buckets are powers of two over this exponent range:
# 2^-20 s (~1 us) .. 2^6 s (64 s) covers RPC round-trips through the
# slowest cold-compile dispatch; observations outside clamp to the
# edge buckets (+Inf catches the rest at render time).
_HIST_EXP_MIN = -20
_HIST_EXP_MAX = 6

_OVERFLOW_LABELS = (("overflow", "true"),)


def max_series() -> int:
    """Per-family label-cardinality cap (``HOROVOD_METRICS_MAX_SERIES``,
    default 256, floor 1).  Sized for the largest legitimate family:
    the multihost (op, size_class) space is 5 ops x ~40 pow2 classes =
    ~200 series; anything past the cap is a runaway label."""
    return env_int("HOROVOD_METRICS_MAX_SERIES", 256, minimum=1)


class _Series:
    __slots__ = ("labels", "value", "buckets", "sum", "count")

    def __init__(self, labels: Tuple[Tuple[str, str], ...]):
        self.labels = labels
        self.value = 0.0
        self.buckets: Dict[int, int] = {}
        self.sum = 0.0
        self.count = 0


class _Handle:
    """One (family, label-set) series; mutation goes through the
    registry lock so concurrent increments never lose updates."""

    __slots__ = ("_registry", "_series", "_kind")

    def __init__(self, registry: "Registry", series: _Series, kind: str):
        self._registry = registry
        self._series = series
        self._kind = kind

    def inc(self, n: float = 1.0):
        if self._kind != "counter":
            raise ValueError("inc() on a %s" % self._kind)
        with self._registry._lock:
            self._series.value += n

    def set(self, v: float):
        if self._kind != "gauge":
            raise ValueError("set() on a %s" % self._kind)
        with self._registry._lock:
            self._series.value = float(v)

    def observe(self, v: float):
        if self._kind != "histogram":
            raise ValueError("observe() on a %s" % self._kind)
        v = float(v)
        e: Optional[int] = _HIST_EXP_MIN
        if v > 2.0 ** _HIST_EXP_MAX:
            e = None  # beyond the top finite bucket: +Inf only
        else:
            while e < _HIST_EXP_MAX and v > 2.0 ** e:
                e += 1
        with self._registry._lock:
            s = self._series
            if e is not None:
                s.buckets[e] = s.buckets.get(e, 0) + 1
            s.sum += v
            s.count += 1

    @property
    def value(self) -> float:
        with self._registry._lock:
            return self._series.value


class _Family:
    __slots__ = ("name", "kind", "help", "series", "overflow_warned")

    def __init__(self, name: str, kind: str, help_text: str):
        self.name = name
        self.kind = kind
        self.help = help_text
        self.series: Dict[Tuple[Tuple[str, str], ...], _Series] = {}
        self.overflow_warned = False


class Registry:
    """Thread-safe process-local metric registry over :data:`NAMES`."""

    def __init__(self):
        self._lock = threading.RLock()
        self._families: Dict[str, _Family] = {}

    def _get(self, kind: str, name: str,
             labels: Dict[str, Any]) -> _Handle:
        decl = NAMES.get(name)
        if decl is None:
            raise KeyError(
                "metric %r is not declared in metrics.NAMES; register "
                "it (kind + help) before instrumenting — the graftlint "
                "metric-unregistered rule enforces this statically"
                % name)
        if decl[0] != kind:
            raise ValueError(
                "metric %r is declared as a %s but used as a %s"
                % (name, decl[0], kind))
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = _Family(name, kind, decl[1])
                self._families[name] = fam
            series = fam.series.get(key)
            if series is None:
                if key != _OVERFLOW_LABELS and \
                        len(fam.series) >= max_series():
                    # Cardinality guard: collapse into one overflow
                    # series instead of growing without bound.
                    if not fam.overflow_warned:
                        fam.overflow_warned = True
                        LOG.warning(
                            "metric %r reached %d label combinations; "
                            "new ones collapse into overflow=\"true\" "
                            "(raise HOROVOD_METRICS_MAX_SERIES if this "
                            "cardinality is intended)",
                            name, max_series())
                    self.counter("metrics_dropped_series_total").inc()
                    key = _OVERFLOW_LABELS
                    series = fam.series.get(key)
                if series is None:
                    series = _Series(key)
                    fam.series[key] = series
            return _Handle(self, series, kind)

    def remove(self, name: str, labels: Dict[str, Any]) -> bool:
        """Drop one series (exact label match) from a family — for
        gauges keyed by a MEMBER identity (``straggler_score{rank=}``)
        whose subject left the fleet: a departed rank's last value
        must not be scraped forever.  Counters/histograms are
        cumulative by contract and should not normally be removed."""
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                return False
            return fam.series.pop(key, None) is not None

    def counter(self, name: str, **labels) -> _Handle:
        return self._get("counter", name, labels)

    def gauge(self, name: str, **labels) -> _Handle:
        return self._get("gauge", name, labels)

    def histogram(self, name: str, **labels) -> _Handle:
        return self._get("histogram", name, labels)

    # -- exposition --------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The full model as plain dicts (pickle/json-safe):
        ``{name: {kind, help, series: [{labels, value} |
        {labels, buckets, sum, count}]}}``."""
        out: Dict[str, Any] = {}
        with self._lock:
            for name, fam in self._families.items():
                rows = []
                for series in fam.series.values():
                    row: Dict[str, Any] = {
                        "labels": dict(series.labels)}
                    if fam.kind == "histogram":
                        row["buckets"] = {
                            str(e): n
                            for e, n in sorted(series.buckets.items())}
                        row["sum"] = series.sum
                        row["count"] = series.count
                    else:
                        row["value"] = series.value
                    rows.append(row)
                out[name] = {"kind": fam.kind, "help": fam.help,
                             "series": rows}
        return out

    def reset(self):
        with self._lock:
            self._families.clear()


_registry = Registry()


def counter(name: str, **labels) -> _Handle:
    return _registry.counter(name, **labels)


def gauge(name: str, **labels) -> _Handle:
    return _registry.gauge(name, **labels)


def histogram(name: str, **labels) -> _Handle:
    return _registry.histogram(name, **labels)


def remove_series(name: str, **labels) -> bool:
    return _registry.remove(name, labels)


def snapshot() -> Dict[str, Any]:
    return _registry.snapshot()


def metrics_snapshot() -> Dict[str, Any]:
    """The in-process metrics model as a dict (``hvd.metrics_snapshot``).
    Works before/without ``hvd.init()`` — the registry is process-local
    and always on."""
    return snapshot()


def series_sum(name: str, **labels) -> float:
    """Sum of one family's series values whose labels match ``labels``
    (a subset match) — the one snapshot-reading convenience for
    benches and tests, so the snapshot schema is consumed in exactly
    one place."""
    fam = snapshot().get(name)
    if not fam:
        return 0.0
    return sum(row.get("value", 0.0) for row in fam.get("series", ())
               if all(row.get("labels", {}).get(k) == v
                      for k, v in labels.items()))


def approx_quantile(model: Dict[str, Any], name: str, q: float,
                    labels: Optional[Dict[str, str]] = None) -> float:
    """Quantile estimate from one log2-bucket histogram family in a
    snapshot ``model``: aggregates every series whose labels contain
    ``labels`` (subset match, like :func:`series_sum`), walks the
    cumulative bucket counts to the ``q``-th observation, and linearly
    interpolates inside the landing bucket — the one percentile
    estimator every bench shares instead of re-deriving its own
    (``serving_bw.py`` p50/p99, ``straggler_ab.py`` latency tails).

    Accuracy is bounded by the bucket geometry: a value is pinned to
    its power-of-two bucket, so the estimate is within 2x of the true
    quantile.  Observations past the top finite bucket (they count
    toward ``count`` but land in no bucket) clamp to the top edge.
    Returns 0.0 when the family is absent or empty."""
    fam = (model or {}).get(name)
    if not fam or fam.get("kind") != "histogram":
        return 0.0
    labels = labels or {}
    buckets: Dict[int, int] = {}
    total = 0
    for row in fam.get("series", ()):
        if not all(row.get("labels", {}).get(k) == str(v)
                   for k, v in labels.items()):
            continue
        total += int(row.get("count", 0))
        for e, n in (row.get("buckets") or {}).items():
            e = int(e)
            buckets[e] = buckets.get(e, 0) + int(n)
    if total <= 0:
        return 0.0
    q = min(max(float(q), 0.0), 1.0)
    target = q * total
    cum = 0.0
    for e in sorted(buckets):
        n = buckets[e]
        if cum + n >= target:
            hi = 2.0 ** e
            lo = 0.0 if e <= _HIST_EXP_MIN else 2.0 ** (e - 1)
            frac = (target - cum) / n if n else 1.0
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        cum += n
    # The target rank lives in the +Inf overflow: every finite edge is
    # below it, so the top finite edge is the least-wrong answer.
    return 2.0 ** _HIST_EXP_MAX


# -- host spans -------------------------------------------------------------

# How many finished spans the process remembers; the oldest goes first.
# A start-up leaves a few dozen spans and three or four compile records a
# program (a few hundred in the transformer cells' set-up).
SPAN_RECORDS_MAX = 8192


class SpanRecord(NamedTuple):
    """One finished span.  ``start`` and ``end`` are epoch seconds (the
    length is ``time.perf_counter``'s); ``parent`` is the ``id`` of the
    span that was open on the same thread, or None."""
    id: int
    parent: Optional[int]
    name: str
    start: float
    end: float
    attributes: Dict[str, Any]


class _OpenSpans(threading.local):
    """This thread's open span ids, innermost last."""

    def __init__(self):
        self.stack: List[int] = []


_span_ids = itertools.count(1)      # next() is atomic under the GIL
_span_records: "collections.deque[SpanRecord]" = collections.deque(
    maxlen=SPAN_RECORDS_MAX)
_span_open = _OpenSpans()


def _span_name(name: str) -> str:
    if name not in scopes.host_spans:
        raise KeyError(
            "span %r is not in the host section of common/scopes.py "
            "(host_spans); name it there first" % (name,))
    return name


def _keep_span(record: SpanRecord):
    histogram("hvd_span_seconds", span=record.name).observe(
        record.end - record.start)
    _span_records.append(record)


def record_span(name: str, start: float, end: float, **attributes):
    """A span that something else timed (JAX's compile stages, the
    package's import), as the record and the observation ``span`` makes
    of its own; the span open on this thread is its parent."""
    stack = _span_open.stack
    _keep_span(SpanRecord(
        next(_span_ids), stack[-1] if stack else None, _span_name(name),
        start, end, attributes))


class span:
    """``with metrics.span(scopes.INIT):`` or ``@metrics.span(...)`` round
    a function: host time of the program's own work (module docstring).
    ``attributes`` (counts: leaves placed, ...) go into the record and may
    be added to while the span is open (``with ... as s:
    s.attributes["leaves"] = n``).  The span closes and records whether
    its body returns or raises."""

    def __init__(self, name: str, **attributes):
        self.name = _span_name(name)
        self.attributes = attributes

    def __enter__(self):
        self._id = next(_span_ids)
        stack = _span_open.stack
        self._parent = stack[-1] if stack else None
        stack.append(self._id)
        self._annotation = None
        if "jax" in sys.modules:
            # Never the reason jax is imported: a tcp worker with host
            # payloads only runs without it.
            import jax
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._start = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _span_open.stack.pop()
        _keep_span(SpanRecord(
            self._id, self._parent, self.name, self._start,
            self._start + seconds, self.attributes))
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            with span(self.name, **self.attributes):
                return fn(*args, **kwargs)
        return timed


def span_records() -> List[SpanRecord]:
    """The finished spans this process remembers, oldest first, as a copy
    (``hvd.span_records()``).  Works before/without ``hvd.init()``."""
    return list(_span_records)


def span_self_seconds(records: List[SpanRecord]) -> Dict[int, float]:
    """``{id: seconds}``: each record's length less what its children
    among ``records`` cover of it (children may overlap one another: the
    compile stages of nested ``jit``s do)."""
    children: Dict[Optional[int], List[SpanRecord]] = {}
    for r in records:
        children.setdefault(r.parent, []).append(r)
    out = {}
    for r in records:
        covered, reach = 0.0, r.start
        for c in sorted(children.get(r.id, ()), key=lambda c: c.start):
            lo, hi = max(c.start, reach), min(c.end, r.end)
            if hi > lo:
                covered += hi - lo
                reach = hi
        out[r.id] = (r.end - r.start) - covered
    return out


# -- Prometheus text rendering --------------------------------------------


def _escape(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _label_text(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join('%s="%s"' % (k, _escape(v))
                     for k, v in sorted(labels.items()))
    return "{%s}" % inner


def _fmt(v: float) -> str:
    f = float(v)
    return repr(int(f)) if f == int(f) else repr(f)


def _render_family(lines: List[str], name: str, fam: Dict[str, Any],
                   extra: Optional[Dict[str, str]] = None):
    for row in fam["series"]:
        labels = dict(row.get("labels") or {})
        if extra:
            for k, v in extra.items():
                # The merge's source label must never CLOBBER a label
                # the series already carries: straggler_score{rank=}
                # is keyed by the SCORED rank — overwriting it with
                # the source tag would collapse every rank's score
                # into duplicate {rank="driver"} series (invalid
                # exposition, meaningless data).
                labels.setdefault(k, v)
        if fam["kind"] == "histogram":
            cum = 0
            for e, n in sorted((int(k), v) for k, v in
                               (row.get("buckets") or {}).items()):
                cum += n
                le = dict(labels, le=_fmt(2.0 ** e))
                lines.append("%s_bucket%s %d"
                             % (name, _label_text(le), cum))
            inf = dict(labels, le="+Inf")
            lines.append("%s_bucket%s %d"
                         % (name, _label_text(inf), row.get("count", 0)))
            lines.append("%s_sum%s %s"
                         % (name, _label_text(labels),
                            _fmt(row.get("sum", 0.0))))
            lines.append("%s_count%s %d"
                         % (name, _label_text(labels),
                            row.get("count", 0)))
        else:
            lines.append("%s%s %s" % (name, _label_text(labels),
                                      _fmt(row.get("value", 0.0))))


def render_merged(models: List[Tuple[str, Dict[str, Any]]]) -> str:
    """One Prometheus-text scrape from several per-process snapshot
    models; each model's series gain a ``rank=<label>`` so the merged
    exposition stays unique per series (HELP/TYPE emitted once per
    family, as the format requires)."""
    lines: List[str] = []
    names: List[str] = []
    for _, model in models:
        for name in model:
            if name not in names:
                names.append(name)
    for name in sorted(names):
        first = next(m[name] for _, m in models if name in m)
        lines.append("# HELP %s %s" % (name, _escape(first["help"])))
        lines.append("# TYPE %s %s" % (name, first["kind"]))
        for rank_label, model in models:
            fam = model.get(name)
            if fam is None or fam["kind"] != first["kind"]:
                continue
            _render_family(lines, name, fam, {"rank": str(rank_label)})
    return "\n".join(lines) + "\n"


def render_prometheus() -> str:
    """This process's registry as Prometheus exposition text."""
    lines: List[str] = []
    model = snapshot()
    for name in sorted(model):
        fam = model[name]
        lines.append("# HELP %s %s" % (name, _escape(fam["help"])))
        lines.append("# TYPE %s %s" % (name, fam["kind"]))
        _render_family(lines, name, fam)
    return "\n".join(lines) + "\n"


# -- structured-event journal ----------------------------------------------

# RLock, not Lock: event() runs inside the SIGTERM drain handler
# (worker.request_drain), which executes on the main thread and may
# interrupt a frame already holding this lock — the exact
# self-deadlock r10 hardened the drain state against.  Re-entrant
# journal writes are safe: each record is one atomic O_APPEND write.
_journal_lock = threading.RLock()
_journal_seq = 0
_journal_fds: Dict[str, int] = {}
_journal_tag: Optional[str] = None
_journal_warned = False


def journal_dir() -> Optional[str]:
    """The JSONL event-journal directory (``HOROVOD_METRICS_DIR``);
    None disables journaling (counters still count)."""
    return os.environ.get("HOROVOD_METRICS_DIR") or None


def set_journal_tag(tag: str):
    """Override the writer tag in the journal filename (the elastic
    driver writes ``events-driver.jsonl``; workers default to their
    rank)."""
    global _journal_tag
    _journal_tag = tag


def _default_tag() -> str:
    rank = os.environ.get("HOROVOD_RANK")
    return "r%s" % rank if rank is not None else "pid%d" % os.getpid()


def event(kind: str, **fields):
    """Record one structured event: bumps ``events_total{kind=}`` and,
    when ``HOROVOD_METRICS_DIR`` is set, appends one rank-stamped JSON
    line (atomic ``O_APPEND`` write, per-process monotonic ``seq``) to
    this process's journal file.  Never raises into the caller."""
    global _journal_seq, _journal_warned
    counter("events_total", kind=kind).inc()
    d = journal_dir()
    if d is None:
        return
    tag = _journal_tag or _default_tag()
    rank = os.environ.get("HOROVOD_RANK")
    try:
        rank = int(rank) if rank is not None else None
    except ValueError:
        rank = None  # malformed env must degrade, never raise here
    with _journal_lock:
        _journal_seq += 1
        record = {"ts": time.time(), "seq": _journal_seq,
                  "rank": rank, "kind": kind}
        for k, v in fields.items():
            record[k] = v
        try:
            path = os.path.join(d, "events-%s.jsonl" % tag)
            fd = _journal_fds.get(path)
            if fd is None:
                os.makedirs(d, exist_ok=True)
                fd = os.open(path,
                             os.O_CREAT | os.O_WRONLY | os.O_APPEND,
                             0o644)
                _journal_fds[path] = fd
            line = json.dumps(record, default=str) + "\n"
            os.write(fd, line.encode())
        except OSError as exc:
            if not _journal_warned:
                _journal_warned = True
                LOG.warning("event journal write failed (%s); further "
                            "events count but are not journaled", exc)


def iter_events(d: Optional[str] = None, merged: bool = False):
    """Yield every journal record under ``d`` (default: the configured
    journal dir) as dicts, across all writers — the read half of the
    round trip, for tests and tooling.

    Default order is (file, line): one writer's stream at a time.
    ``merged=True`` interleaves ALL writers into one stream sorted by
    ``(ts, writer, seq)`` and stamps each record with its ``writer``
    tag (the ``events-<writer>.jsonl`` filename segment), so cross-rank
    event correlation — a drain notice against the straggler detection
    that caused it, a fault fire against the drift it produced — needs
    no ad-hoc per-file stitching in every consumer.  ``seq`` is only
    per-process monotonic, so it breaks ties within a writer; across
    writers the wall clock (and then the writer tag, for determinism)
    orders the merge."""
    d = d if d is not None else journal_dir()
    if d is None or not os.path.isdir(d):
        return

    def _records():
        for name in sorted(os.listdir(d)):
            if not name.startswith("events-") \
                    or not name.endswith(".jsonl"):
                continue
            writer = name[len("events-"):-len(".jsonl")]
            with open(os.path.join(d, name), "r",
                      encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        yield writer, json.loads(line)
                    except ValueError:
                        continue  # torn final line of a killed writer

    if not merged:
        for _writer, record in _records():
            yield record
        return
    rows = [(record.get("ts", 0.0), writer, record.get("seq", 0), record)
            for writer, record in _records()]
    rows.sort(key=lambda r: (r[0], r[1], r[2]))
    for ts, writer, _seq, record in rows:
        out = dict(record)
        out["writer"] = writer
        yield out


def reset():
    """Drop every series, the finished spans, the journal fd cache and
    the seq counter (tests)."""
    global _journal_seq, _journal_tag, _journal_warned
    _registry.reset()
    _span_records.clear()
    with _journal_lock:
        for fd in _journal_fds.values():
            try:
                os.close(fd)
            except OSError:
                pass
        _journal_fds.clear()
        _journal_seq = 0
    _journal_tag = None
    _journal_warned = False
