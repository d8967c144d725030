"""Multihost (multi-controller SPMD) collective execution.

The TPU-native realisation of the reference's MPI-control/NCCL-payload
split (``horovod/common/ops/nccl_operations.cc`` executing payloads while
the MPI/Gloo controller negotiates, SURVEY.md §2.6): one process per
host, every process a member of one global ``jax`` runtime
(``jax.distributed.initialize``).  The native TCP core negotiates
readiness and a single cross-rank execution order; this module's
executor drains the negotiated group records and runs each collective as
a compiled XLA program over the GLOBAL device mesh — ICI/DCN on TPU
pods, gloo on the CPU test world.

Rank semantics: one Horovod rank per process (host), exactly the
reference's model.  A process's collective input is ITS tensor.  The
eager payload plane has two gears: small payloads ride a
one-device-per-process mesh (axis "proc", device 0 of every member),
and payloads at or above ``HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD``
ride a proc x local mesh spanning EVERY local chip — chunk j of the
payload lives on local device j, cross-host reduction moves 1/k of the
bytes per chip, and a local ``all_gather`` reassembles the result over
intra-host ICI (the reference's NCCL hierarchical allreduce,
``HOROVOD_HIERARCHICAL_ALLREDUCE``).  jit-path data parallelism
(``jax/data_parallel.py``) keeps using every addressable device.

Ordering contract: all member processes must issue the same global
collective programs in the same order or the runtime deadlocks — that is
precisely what the control plane guarantees, and why eager collectives
may ONLY be executed by this engine's single executor thread (the role
the reference's background thread plays for NCCL kernels).
"""

from __future__ import annotations

import collections
import logging
import queue as queue_mod
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..common import faultline, metrics, resilience
from ..common.config import Config
from ..utils.timeline import Timeline
from . import fastpath, xla_ops
from .engine import (CollectiveDeadlineExceeded, CollectiveHandle,
                     HorovodInternalError)
from .xla_ops import (ADASUM, AVERAGE, MAX, MIN, PRODUCT, SUM,
                      alltoall_chunk_reduce, product_allreduce)

LOG = logging.getLogger("horovod_tpu")


from .xla_ops import uneven_chunks as _uneven_chunks

# Above this many bytes, exact pow2 bucketing would waste up to 2x wire
# bytes on padding; large payloads round up to the next multiple of it
# instead (pad waste bounded by the threshold, still a small number of
# size classes for the executable cache).
_POW2_BUCKET_MAX_BYTES = 4 << 20


def _size_class(n_elems: int, itemsize: int) -> int:
    """Padded element count keying a packed collective executable:
    power-of-two below ``_POW2_BUCKET_MAX_BYTES`` (the recompile-cliff
    protection for shape-varying bursts), coarse linear steps above it
    (bounded pad waste for big tensors)."""
    from .engine import _bucket
    step = max(_POW2_BUCKET_MAX_BYTES // max(int(itemsize), 1), 1)
    n = max(int(n_elems), 1)
    if n <= step:
        return _bucket(n)
    return -(-n // step) * step


def _is_device_array(x) -> bool:
    import jax
    return isinstance(x, jax.Array)


def _pow2_class(nbytes: int) -> str:
    """Pow2-ceil byte class labeling per-collective metric series: ~40
    distinct values per op across any realistic payload range, so the
    full 5-op (op, size_class) space (~200 combos worst case) stays
    inside the default HOROVOD_METRICS_MAX_SERIES cap of 256."""
    n = max(int(nbytes), 1)
    return str(1 << (n - 1).bit_length())


def _count_path(op: str, nbytes: int, hier: bool, codec=None,
                wire_bytes=None):
    """Path attribution for one executed collective: which plane moved
    the bytes (hier = proc x local mesh, flat = one-device-per-process)
    and what actually hit the WIRE.  ``mh_bus_bytes_total`` is a
    wire-bytes counter: with a cross-host codec active it records the
    compressed ``wire_bytes`` (payload elements at the wire itemsize
    plus scale overhead), otherwise the pre-padding payload bytes —
    the self-attribution the BENCH compression A/B reads."""
    path = "hier" if hier else "flat"
    metrics.counter("mh_collective_path_total", op=op, path=path).inc()
    wire = (int(wire_bytes) if codec is not None and wire_bytes
            else max(int(nbytes), 0))
    metrics.counter("mh_bus_bytes_total", op=op, path=path).inc(
        max(wire, 0))
    if codec is not None and wire_bytes:
        metrics.counter("mh_compressed_collectives_total", op=op,
                        codec=codec.name).inc()
        metrics.gauge("mh_compression_ratio", op=op,
                      codec=codec.name).set(
            round(max(int(nbytes), 0) / float(wire_bytes), 4))


class _WireCodec:
    """Resolved cross-host wire codec (HOROVOD_CROSS_HOST_COMPRESSION):
    ``kind`` 'cast' rides the existing cross-host legs natively in the
    narrower ``wire`` dtype (fp16/bf16 arithmetic is well-defined on
    every backend); ``kind`` 'quant' (int8/fp8) never does arithmetic
    in the wire dtype — wire payloads move via exchange legs (two-phase
    reduce-scatter/all-gather for allreduce, masked byte-psum for
    broadcast, all_to_all/all_gather with per-sender scales elsewhere)
    and dequantize to f32 on the far side."""

    __slots__ = ("name", "kind", "wire")

    def __init__(self, name: str, kind: str, wire):
        self.name = name
        self.kind = kind
        self.wire = np.dtype(wire)


def _resolve_codec(name: str) -> Optional[_WireCodec]:
    """Config codec string -> _WireCodec (None for 'none').  fp8 on a
    jax without float8 dtypes downgrades LOUDLY to a bf16 wire (2x,
    not 4x) instead of silently shipping full precision."""
    import jax.numpy as jnp
    if name in (None, "", "none"):
        return None
    if name == "fp16":
        return _WireCodec("fp16", "cast", np.float16)
    if name == "bf16":
        return _WireCodec("bf16", "cast", jnp.bfloat16)
    if name == "int8":
        return _WireCodec("int8", "quant", np.int8)
    if name == "fp8":
        from ..jax.compression import FP8_WIRE_DTYPE
        if FP8_WIRE_DTYPE is None:
            LOG.error(
                "HOROVOD_CROSS_HOST_COMPRESSION=fp8: this jax version "
                "has no float8_e4m3fn dtype; falling back to a bf16 "
                "wire (2x reduction instead of 4x)")
            return _WireCodec("fp8-as-bf16", "cast", jnp.bfloat16)
        return _WireCodec("fp8", "quant", FP8_WIRE_DTYPE)
    raise ValueError("unknown cross-host compression codec %r" % name)


def _axis0_reduce(deq, red_op, size: int):
    """Reduce f32 dequantized contributions [members, n] -> [n] per
    the negotiated op (AVERAGE divides by the full member count, like
    the uncompressed planes; join cannot reach the compressed leg)."""
    import jax.numpy as jnp
    if red_op in (SUM, AVERAGE):
        r = jnp.sum(deq, axis=0)
        if red_op == AVERAGE:
            r = r / size
    elif red_op == MIN:
        r = jnp.min(deq, axis=0)
    elif red_op == MAX:
        r = jnp.max(deq, axis=0)
    elif red_op == PRODUCT:
        r = jnp.prod(deq, axis=0)
    else:
        raise NotImplementedError(red_op)
    return r


def _chunked_segments(p, n_items, item_start, item_valid, bc, k):
    """Segment list staging k j-major local chunks of ``n_items``
    padded items: local chunk j carries, for every item m, elements
    [j*bc, (j+1)*bc) of item m's (bc*k)-padded span.  Items are slices
    of ``p`` at ``item_start[m]`` with ``item_valid[m]`` live elements;
    the remainder pads with zeros.  Shared by the hierarchical alltoall
    (items = destination blocks) and reducescatter (items = member
    segments) staging paths."""
    segs = []
    for j in range(k):
        for m in range(n_items):
            lo = j * bc
            take = min(max(int(item_valid[m]) - lo, 0), bc)
            if take:
                segs.append((p, int(item_start[m]) + lo, take))
            if take < bc:
                segs.append((None, 0, bc - take))
    return segs


def adasum_combine(v, axis_name: str, size: int):
    """Device-resident Adasum over a mesh axis (per-shard code).

    The reference's GPU-resident Adasum (SURVEY §2.2,
    ``adasum_gpu_operations.cc``) keeps payloads on the accelerator;
    here the recursive-halving tree of ``utils/adasum.py`` runs as
    log2(size) ``ppermute`` exchange rounds over the axis: partners at
    XOR-stride distance swap full vectors, both compute the SAME
    symmetric merge, and every shard converges to the tree result —
    bytes = n·log2(N) over ICI, no host bounce.  Merge order matches
    ``utils/adasum.adasum_reduce_stacked`` (strides n/2, n/4, …, 1 =
    the stacked halving tree), including the per-round cast back to
    the payload dtype.
    """
    import jax
    import jax.numpy as jnp
    if size & (size - 1):
        raise HorovodInternalError(
            "Adasum requires a power-of-two member count (got %d), as "
            "in the reference's recursive-halving implementation" % size)
    from ..utils.adasum import adasum_pair
    stride = size // 2
    while stride >= 1:
        perm = [(i, i ^ stride) for i in range(size)]
        w = jax.lax.ppermute(v, axis_name, perm)
        # adasum_pair is the single source of truth for the merge rule
        # (f32 dots, epsilon guard, payload-dtype round-trip) — both
        # partners compute the SAME symmetric merge, so every shard
        # converges to the host tree's result.
        v = adasum_pair(v, w)
        stride //= 2
    return v


class GlobalMeshCollectives:
    """Compiled XLA collectives over the member processes' devices.

    The base plane is the reference's one-accelerator-per-rank NCCL
    model (``ops/nccl_operations.cc``): each member process owns one
    mesh device (its first addressable device), payloads stay
    device-resident end to end — ``jax.Array`` inputs are staged with
    a device-to-device put (no host bounce), numpy inputs with a
    single host-to-device transfer — and every collective is explicit
    HLO (``psum`` / ``all_gather`` / ``all_to_all`` / ``psum_scatter``
    under ``shard_map``), not a host-staged emulation.  Large
    allreduces additionally shard across every LOCAL chip
    (``_hier_allreduce``), so all local ICI/DCN links carry payload
    instead of chip 0's alone.

    Every method is a *collective program*: all member processes must
    call it with consistent negotiated arguments.  Executables are
    cached per (op, dtype, shape, params) so steady state dispatches
    without retracing; staged inputs are donated, so XLA may reuse the
    payload buffer for the result (the reference's persistent fusion
    buffer, expressed as buffer donation).
    """

    def __init__(self, member_procs: Optional[Sequence[int]] = None,
                 name: str = "global"):
        import jax
        from jax.sharding import Mesh

        all_procs = sorted({d.process_index for d in jax.devices()})
        self.procs = (list(member_procs) if member_procs is not None
                      else all_procs)
        self.size = len(self.procs)
        self.name = name
        self.my_idx = (self.procs.index(jax.process_index())
                       if jax.process_index() in self.procs else -1)
        by_proc: Dict[int, list] = {}
        for d in sorted(jax.devices(), key=lambda d: d.id):
            by_proc.setdefault(d.process_index, []).append(d)
        missing = [p for p in self.procs if p not in by_proc]
        if missing:
            raise HorovodInternalError(
                "process set %r members %s have no addressable JAX "
                "devices; every member process must expose at least "
                "one device" % (name, missing))
        devs = [by_proc[p][0] for p in self.procs]
        self.mesh = Mesh(np.asarray(devs), ("proc",))
        self.device = devs[self.my_idx] if self.my_idx >= 0 else None  # graftlint: spmd-uniform -- device HANDLE: names where this process STAGES payload bytes (per-rank placement is the SPMD model); no routing decision ever reads it
        from ..common.config import Config as _Cfg
        cfg = _Cfg.from_env()
        # Multi-chip payload plane (reference hierarchical allreduce,
        # SURVEY §2.2 NCCL row): a 2-D proc x local mesh over every
        # member's local chips.  k is the least local device count
        # across members (the mesh must be rectangular); k == 1
        # degenerates to the one-device plane.
        k = min(len(by_proc[p]) for p in self.procs)
        self._hier_mode = cfg.hierarchical_allreduce
        self._hier_threshold = int(cfg.hierarchical_allreduce_threshold)
        self.local_size = k if self._hier_mode != "off" else 1
        self.mesh2 = None
        self.local_devices: list = []
        if self.local_size > 1:
            devs2 = np.asarray(
                [[by_proc[p][j] for j in range(k)] for p in self.procs])
            self.mesh2 = Mesh(devs2, ("proc", "local"))
            self.local_devices = (list(devs2[self.my_idx])
                                  if self.my_idx >= 0 else [])
        # Cross-host wire codec (r12): consulted at the SAME gate as
        # _hier_eligible — only the hier plane has a distinct DCN leg
        # to compress; in-host reassembly stays in the payload dtype.
        # Reduce ops (Sum/Average) go through error-feedback residuals
        # keyed per bucket so quantization error is delayed, not lost.
        self._codec = (_resolve_codec(cfg.cross_host_compression)
                       if self.local_size > 1 else None)
        if (self._codec is None
                and cfg.cross_host_compression != "none"):
            LOG.warning(
                "HOROVOD_CROSS_HOST_COMPRESSION=%s is set but the "
                "hierarchical plane is unavailable (one local device, "
                "or mode 'off'): payloads stay full precision",
                cfg.cross_host_compression)
        self._quantizer = None
        self._ef = None
        if self._codec is not None and self._codec.kind == "quant":
            # fp8 uses the absmax-SCALED e4m3 quantizer here, not the
            # framework-surface plain cast: an unscaled cast NaNs past
            # +-448, and the engine must be range-safe for any payload.
            from ..jax.compression import (ErrorFeedback, Int8Quantizer,
                                           ScaledFP8Quantizer)
            self._quantizer = (Int8Quantizer if self._codec.name == "int8"
                               else ScaledFP8Quantizer)
            self._ef = ErrorFeedback(self._quantizer,
                                     cfg.compression_residual_buckets)
        # Leg-2 (post-reduce) error-feedback residuals of the two-phase
        # quantized allreduce: mesh-sharded device arrays carried across
        # steps as donated program inputs/outputs, LRU-capped like the
        # eager residual buckets.  Executor-thread only.
        self._res2: "collections.OrderedDict" = collections.OrderedDict()
        self._res2_cap = max(int(getattr(
            cfg, "compression_residual_buckets", 64)), 1)
        # Collective-plan plane (persistent autotuned plans): per-(op,
        # size_class) routing decisions — hier/flat leg + codec
        # engagement — from the plan loaded/adopted at init().  None
        # when the plane is disabled or this mesh's topology differs
        # from the tuned fingerprint (process-set sub-meshes); routing
        # then falls back to the global byte-threshold gate unchanged.
        self._plan_ctl = None
        try:
            from ..utils import plancache
            self._plan_ctl = plancache.controller_for(
                self.size, self.local_size,
                getattr(devs[0], "device_kind", devs[0].platform))
        except Exception:  # noqa: BLE001 - plans must never block a mesh
            self._plan_ctl = None
        # Capacity-bounded LRU like the in-process engine (the
        # reference's HOROVOD_CACHE_CAPACITY): long jobs with varying
        # shapes must not grow compiled programs without bound.
        from .executable_cache import ExecutableCache
        self._fns = ExecutableCache(cfg.cache_capacity)
        # key -> lowered HLO text, populated when HVD_TPU_DUMP_HLO=1
        # (lets tests assert the real collective ops are emitted).
        self.hlo: Dict[tuple, str] = {}
        # Count of host (numpy) stagings — device payloads must never
        # bump this (the device-residency contract, testable).
        self.host_stages = 0

    # -- plumbing ----------------------------------------------------------

    def _sharding(self, spec):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, spec)

    def _stage(self, arr, row_shape, dtype):
        """Stage this process's contribution as its row of a global
        [size, *row_shape] array sharded over ``proc``.

        ``jax.Array`` payloads stay on device (at most a local reshape
        + device-to-device put); numpy payloads cross the host boundary
        exactly once; ``None`` (a joined rank's missing entry)
        synthesizes zeros directly on the mesh device.  The staged row
        is always a fresh buffer, so compiled programs may donate it.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        shape = (1,) + tuple(int(d) for d in row_shape)
        if arr is None:
            with jax.default_device(self.device):
                row = jnp.zeros(shape, dtype)
        elif _is_device_array(arr):
            row = jax.device_put(jnp.reshape(arr, shape), self.device)
        else:
            self.host_stages += 1
            row = jax.device_put(
                np.ascontiguousarray(np.asarray(arr)).reshape(shape),
                self.device)
        return jax.make_array_from_single_device_arrays(
            (self.size,) + shape[1:], self._sharding(P("proc")), [row])

    def _replicated(self, garr):
        """This process's view of a replicated (P()) program output, as
        a single-device jax.Array — no host transfer."""
        return garr.addressable_shards[0].data

    def _pack_flat(self, segments, total: int, bucket: int, np_dtype):
        """One padded flat [bucket] buffer on this process's mesh
        device.

        ``segments`` is a list of (payload, start_elem, n_elems) flat
        slices laid out back to back (payload None -> zeros); the
        bucket padding keys the compiled program by SIZE CLASS instead
        of exact composition — the reference's persistent fusion
        buffer, shared by the packed allreduce and the per-op packed
        paths.  Each DISTINCT payload flattens exactly once (device
        payloads: one local reshape/device_put, no host transit; numpy
        payloads: one host crossing, one ``host_stages`` bump), however
        many segments slice it."""
        import jax
        import jax.numpy as jnp

        flats: Dict[int, object] = {}

        def flat_of(payload):
            fid = id(payload)
            f = flats.get(fid)
            if f is None:
                if _is_device_array(payload):
                    f = jax.device_put(jnp.reshape(payload, (-1,)),
                                       self.device)
                else:
                    self.host_stages += 1
                    f = jnp.asarray(np.ascontiguousarray(
                        np.asarray(payload)).reshape(-1))
                flats[fid] = f
            return f

        parts = []
        with jax.default_device(self.device):
            for payload, start, n in segments:
                if n == 0:
                    continue
                if payload is None:
                    parts.append(jnp.zeros((n,), np_dtype))
                else:
                    f = flat_of(payload)
                    parts.append(
                        f if start == 0 and n == f.shape[0]
                        else jax.lax.slice_in_dim(f, start, start + n))
            if bucket > total:
                parts.append(jnp.zeros((bucket - total,), np_dtype))
            row = (jnp.concatenate(parts) if len(parts) > 1
                   else parts[0] if parts
                   else jnp.zeros((bucket,), np_dtype))
            if row.dtype != np_dtype:
                row = row.astype(np_dtype)
        return row

    def _stage_flat_padded(self, segments, total: int, bucket: int,
                           np_dtype):
        """``_pack_flat`` staged as one row of the proc-sharded global
        array."""
        return self._stage(
            self._pack_flat(segments, total, bucket, np_dtype),
            (bucket,), np_dtype)

    def _my_row(self, garr):
        """This process's row of a P('proc') program output."""
        return garr.addressable_shards[0].data[0]

    def _hier_eligible(self, nbytes: int) -> bool:
        """Route this payload over the proc x local mesh?  One shared
        gate for all five eager ops (the reference's NCCL ops drive
        every local accelerator's links for every collective, SURVEY
        §2.2): more than one local chip, and either mode 'on' or the
        payload at/above the hierarchical threshold."""
        return (self.local_size > 1
                and (self._hier_mode == "on"
                     or int(nbytes) >= self._hier_threshold))

    def _route(self, op: str, nbytes: int):  # graftlint: hot-path
        """(use_hier, engage_codec) for one dispatch: the per-(op,
        size_class) plan wins when the plan plane is active (explicit
        gate envs win over it and suppress pinning, resolved at
        controller construction), otherwise the global byte-threshold
        gate with the codec left to ``_wire_codec``.  Every member
        resolves identically — the plan is shared via the cache blob /
        KV adoption — so negotiated programs never diverge."""
        cls = _pow2_class(nbytes)
        hier = self._hier_eligible(nbytes)
        if self._plan_ctl is not None:
            hier, codec_on = self._plan_ctl.route(op, cls, hier)
        else:
            codec_on = True
        # The resilience demotion map is authoritative over every
        # other gate: a demoted class is flat on EVERY member (the
        # map only ever changes through the rank-0 KV verdict), even
        # if a stale plan entry or env pin still says hier.
        if hier and resilience.demoted(op, cls):
            return False, codec_on
        return hier, codec_on

    def _guarded(self, op: str, nbytes: int, run_hier, run_flat,
                 payloads=(), codec=None):  # graftlint: hot-path
        """Run a hier leg under the data-plane guard
        (:func:`resilience.run_hier_leg`: injection sites, wire
        integrity, transient retry under the group deadline), falling
        back to the flat program for THIS group on retry exhaustion.

        The fallback is rank-local by design: the fault shapes the
        guard absorbs exhaust identically on every member (shared DCN
        link, config-driven codec faults, symmetric injection), and a
        genuinely asymmetric exhaustion diverges the programs only
        until the group deadline poisons the engine and elastic
        restores.  Persistent routing never changes here — only the
        rank-0 KV verdict in ``check_degraded_routes`` demotes a
        class."""
        cls = _pow2_class(nbytes)
        try:
            return resilience.run_hier_leg(
                op, cls, run_hier, payloads=payloads,
                quantized=codec is not None and codec.kind == "quant")
        except resilience.LegDegraded as exc:
            LOG.warning(
                "multihost %s[%s]: hier leg degraded (%s); this group "
                "falls back to the flat plane", op, cls, exc.cause)
            return run_flat()

    def _stage_hier(self, segments, total: int, chunk: int, np_dtype):
        """Stage ``segments`` as this process's (1, k, chunk) slab of a
        [size, k, chunk] array over the proc x local mesh: the packed
        flat [k*chunk] buffer splits j-major, chunk j committed to
        local device j via ``_stage_hier_rows`` (one device-to-device
        put per chip; numpy payloads cross the host once inside
        ``_pack_flat``)."""
        k = self.local_size
        flat = self._pack_flat(segments, total, chunk * k, np_dtype)
        return self._stage_hier_rows(flat.reshape(k, chunk))

    def _wire_codec(self, np_dtype, red_op=None) -> Optional[_WireCodec]:
        """The active cross-host codec for a hier-path payload of
        ``np_dtype``: the configured codec when the payload is floating
        and the wire dtype is actually narrower; None otherwise (a
        discrete payload would be corrupted, a same-width cast wins
        nothing).  Product reductions are excluded from the QUANT
        codecs: an element below its chunk's absmax/254 quantizes to
        exactly 0 and zeroes the whole product — unbounded relative
        error, unlike the scale/2-bounded Sum/Average/Min/Max cases."""
        c = self._codec
        if c is None:
            return None
        if red_op == PRODUCT and c.kind == "quant":
            return None
        import jax.numpy as jnp
        dt = np.dtype(np_dtype)
        if not jnp.issubdtype(dt, jnp.floating):
            return None
        if c.wire.itemsize >= dt.itemsize:
            return None
        return c

    def _wire_nbytes(self, codec: _WireCodec, n_elems: int) -> int:
        """Bytes this payload puts on the cross-host wire under
        ``codec``: payload elements at the wire itemsize, plus the
        per-chunk f32 absmax scales of the quantizing codecs (bounded
        by two scale sets per local chunk — the two-phase allreduce
        carries one per leg)."""
        w = int(n_elems) * codec.wire.itemsize
        if codec.kind == "quant":
            w += self.local_size * 8
        return w

    def _stage_hier_rows(self, rows2d):  # graftlint: hot-path
        """Stage an eagerly-encoded per-chunk [k, m] device array (row
        j -> local device j) as this process's (1, k, m) slab of a
        [size, k, m] proc x local array — the wire-staging seam: what
        lands here is exactly what crosses DCN."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        k = self.local_size
        m = int(rows2d.shape[1])
        rows = [jax.device_put(
            jax.lax.slice_in_dim(rows2d, j, j + 1).reshape(1, 1, m),
            dev) for j, dev in enumerate(self.local_devices)]
        return jax.make_array_from_single_device_arrays(
            (self.size, k, m),
            NamedSharding(self.mesh2, P("proc", "local")), rows)

    def _quant_encode(self, flat, ef_key=None):  # graftlint: hot-path
        """Eagerly encode a packed flat [k*m] buffer for the wire:
        one row per local chip, quantized per row (absmax int8 /
        absmax-scaled e4m3) — through the error-feedback residual
        keyed by ``ef_key`` for the linear reduce ops, plain for data
        movement.  Returns (wire [k, m], scales [k, 1] f32); the ones
        fallback covers any scale-free ctx shape."""
        import jax.numpy as jnp
        rows = flat.reshape(self.local_size, -1)
        if ef_key is not None and self._ef is not None:
            wire, ctx = self._ef.compress(rows, bucket=ef_key)
        else:
            wire, ctx = self._quantizer.compress(rows)
        if isinstance(ctx, tuple):
            scales = ctx[0].astype(jnp.float32).reshape(
                self.local_size, 1)
        else:
            scales = jnp.ones((self.local_size, 1), jnp.float32)
        return wire, scales

    def _wire_residual2(self, key, slice_n: int):
        """Leg-2 residual carrier for the two-phase quantized
        allreduce: the [size, k, slice_n] f32 array the previous step's
        program emitted (donated back in this step), or zeros on first
        touch / geometry change."""
        import jax.numpy as jnp
        arr = self._res2.get(key)
        if arr is not None and arr.shape[2] == int(slice_n):
            self._res2.move_to_end(key)
            return arr
        return self._stage_hier_rows(
            jnp.zeros((self.local_size, int(slice_n)), jnp.float32))

    def _store_residual2(self, key, arr):
        self._res2[key] = arr
        self._res2.move_to_end(key)
        while len(self._res2) > self._res2_cap:
            self._res2.popitem(last=False)

    def _compiled(self, key, build, example_args=None, notify=None):
        """``notify`` is the per-dispatch cold-compile callback,
        threaded through the call chain from the engine's dispatch (it
        brackets AOT compiles so the execution watchdog never charges
        compile time to the watched window).  It is an explicit
        argument, NOT instance state: two executors dispatching through
        one mesh object must not cross their callbacks."""
        fn = self._fns.lookup(key)
        if fn is None:
            fn = build()
            import os
            if notify is not None:
                notify("begin")
            try:
                if example_args is not None:
                    # AOT lower+compile HERE (not lazily at the first
                    # call): compilation is local and can be long;
                    # doing it inside this helper lets the engine's
                    # watchdog distinguish compiling (healthy) from a
                    # wedged execution (member died after negotiation).
                    lowered = fn.lower(*example_args)
                    if os.environ.get("HVD_TPU_DUMP_HLO"):
                        self.hlo[key] = lowered.as_text()
                    fn = lowered.compile()
            finally:
                if notify is not None:
                    notify("end")
            self._fns.put(key, fn)
        return fn

    def _collective_jit(self, fn, n_args, out_spec, mesh=None,
                        in_spec=None):
        """shard_map + jit with every staged input donated."""
        import jax
        from jax.sharding import PartitionSpec as P
        # The static replication/vma checker cannot see through the
        # axis_index masking / per-process static slicing these
        # programs use; the negotiation contract guarantees consistent
        # collectives, so disable it.
        mapped = jax.shard_map(
            fn, mesh=mesh if mesh is not None else self.mesh,
            in_specs=(in_spec if in_spec is not None
                      else P("proc"),) * n_args,
            out_specs=out_spec, check_vma=False)
        return jax.jit(mapped, donate_argnums=tuple(range(n_args)))

    @staticmethod
    def _scaled(v, factor):
        return v if factor == 1.0 else v * np.asarray(factor, v.dtype)

    # -- collectives -------------------------------------------------------

    def _reduce_block(self, v, red_op, prescale, postscale, divisor):
        """Per-shard reduction body shared by allreduce flavors."""
        import jax
        import jax.numpy as jnp
        v = self._scaled(v, prescale)
        if red_op == ADASUM:
            r = adasum_combine(v, "proc", self.size)
        elif red_op in (SUM, AVERAGE):
            r = jax.lax.psum(v, "proc")
            if red_op == AVERAGE:
                r = (r / divisor).astype(v.dtype) if \
                    jnp.issubdtype(v.dtype, jnp.floating) \
                    else r // divisor
        elif red_op == MIN:
            r = jax.lax.pmin(v, "proc")
        elif red_op == MAX:
            r = jax.lax.pmax(v, "proc")
        elif red_op == PRODUCT:
            # Exact bytes-proportional product (reduce-scatter +
            # tiled all_gather, ~2x like Sum — not N x all_gather).
            r = product_allreduce(
                v.reshape(-1), "proc", self.size).reshape(v.shape)
        else:
            raise NotImplementedError(red_op)
        return self._scaled(r, postscale)

    def fused_allreduce(self, payloads: Sequence, lengths: Sequence[int],
                        dtype, red_op: str = SUM, prescale: float = 1.0,
                        postscale: float = 1.0, notify=None,
                        names: Optional[Sequence[str]] = None
                        ) -> List:  # graftlint: hot-path
        """One compiled program reducing a negotiated fusion group.

        ``payloads[i]`` is this process's flat contribution for entry i
        (jax.Array, numpy, or None for a joined rank's missing entry);
        ``lengths`` are the negotiated element counts.  The program
        takes one [size, n_i] input per entry and emits one psum per
        entry — XLA's all-reduce combiner packs them into a single
        fused collective (the compiler-managed fusion buffer).  Returns
        per-entry flat device arrays, replicated on the mesh device.
        """
        lengths = [int(n) for n in lengths]
        if red_op != SUM and any(p is None for p in payloads):
            # Zero fill is only the identity for Sum: a joined rank's
            # zeros clamp Min to <=0 and annihilate Product.  The
            # controller rewrites Average->Sum with a live-count divisor
            # and rejects the rest at negotiation; a direct caller that
            # reaches here with None + non-Sum must fail loudly, not
            # corrupt the reduction (reference join semantics).
            raise HorovodInternalError(
                "joined-rank (None) payload with op=%s: zero fill is "
                "only neutral for Sum" % red_op)
        if len(lengths) > 1 and red_op != ADASUM:
            # Adasum must stay per-entry: its dot-product combine over
            # a packed bucket would merge ACROSS tensors (wrong math),
            # so fused Adasum groups compile the direct multi-input
            # program with one combine per entry.
            return self._fused_allreduce_packed(
                payloads, lengths, dtype, red_op, prescale, postscale,
                notify)
        hier = codec_on = False
        if len(lengths) == 1 and red_op != ADASUM:
            hier, codec_on = self._route(
                "allreduce", lengths[0] * np.dtype(dtype).itemsize)
        def run_flat() -> List:
            key = ("fused_allreduce", tuple(lengths),
                   str(np.dtype(dtype)), red_op, float(prescale),
                   float(postscale))
            size = self.size

            def build():
                def fn(*xs):
                    return tuple(
                        self._reduce_block(x.reshape(-1), red_op,
                                           prescale, postscale, size)
                        for x in xs)
                from jax.sharding import PartitionSpec as P
                return self._collective_jit(fn, len(lengths), P())

            _count_path("allreduce",
                        sum(lengths) * np.dtype(dtype).itemsize, False)
            staged = [self._stage(p, (n,), dtype)
                      for p, n in zip(payloads, lengths)]
            outs = self._compiled(key, build, staged, notify)(*staged)
            return [self._replicated(o) for o in outs]

        if hier:
            # Multi-chip hierarchical path: every local chip moves 1/k
            # of the bytes cross-host instead of chip 0 moving all of
            # them.  Adasum is excluded — its combine is dot-product
            # based over the WHOLE vector, so per-chunk combines would
            # change the math (it stays on the one-device plane).
            codec = (self._wire_codec(dtype, red_op) if codec_on
                     else None)
            _count_path("allreduce",
                        lengths[0] * np.dtype(dtype).itemsize, True,
                        codec,
                        self._wire_nbytes(codec, lengths[0])
                        if codec else None)
            return self._guarded(
                "allreduce", lengths[0] * np.dtype(dtype).itemsize,
                lambda: [self._hier_allreduce(
                    payloads[0], lengths[0], dtype, red_op, prescale,
                    postscale, notify, codec,
                    names[0] if names else None)],
                run_flat, payloads=(payloads[0],), codec=codec)
        return run_flat()

    def _hier_allreduce(self, p, n: int, dtype, red_op, prescale,
                        postscale, notify=None, codec=None,
                        ef_name=None):  # graftlint: hot-path
        """Hierarchical allreduce over the proc x local mesh — the
        reference's ``HOROVOD_HIERARCHICAL_ALLREDUCE`` (NCCL
        reduce-scatter intra-node + cross-node allreduce + allgather,
        SURVEY §2.2) with one-contribution-per-HOST rank semantics:

        1. scatter (staging): the flat payload splits into k chunks,
           chunk j committed to local device j — the intra-host
           reduce-scatter degenerates to a split because each host has
           exactly ONE contribution;
        2. cross-host reduce: chunk j psums over the ``proc`` axis —
           k parallel collectives, each moving n/k bytes over that
           chip's own ICI/DCN links (the bandwidth win: all local
           chips' links drive traffic instead of chip 0's alone);
        3. ``all_gather`` over the ``local`` axis reassembles the full
           reduced vector on every local chip — intra-host ICI.

        Returns the reduced flat [n] device array (replica on this
        process's first local device, like the one-device plane).
        """
        import jax
        from jax.sharding import PartitionSpec as P

        import jax.numpy as jnp

        k = self.local_size
        chunk = -(-int(n) // k)
        padded = chunk * k
        np_dtype = np.dtype(dtype)
        size = self.size
        if codec is not None and codec.kind == "quant":
            # Two-phase compressed exchange (the 1-bit-Adam scheme):
            # leg 1 all_to_all's each chip's quantized chunk slices
            # and dequant-reduces MY slice in f32 (a compressed
            # reduce-scatter); leg 2 requantizes the reduced slice —
            # through a SECOND error-feedback residual for the linear
            # ops, carried across steps as a donated program
            # input/output — and all_gathers it back (a compressed
            # all-gather).  Per-chip DCN traffic is ~2*(p-1)/p wire
            # bytes at ANY world size: the uncompressed psum's
            # movement shape at 1/4 the byte width, never the O(p)
            # blow-up of all-gathering the full wire payload.
            chunk = -(-chunk // size) * size  # leg-1 slices split evenly
            padded = chunk * k
            slice_n = chunk // size
            linear = red_op in (SUM, AVERAGE)
            flat = self._pack_flat([(p, 0, int(n))], int(n), padded,
                                   np_dtype)
            # Residuals key by the tensor NAME when the caller has one
            # (each named gradient keeps its OWN delayed error — EF
            # theory wants per-tensor residuals); the packed fusion
            # bucket has no stable name and falls back to its size
            # class, the reference fusion-buffer granularity.
            ef_key = (("allreduce", padded, str(np_dtype), ef_name)
                      if linear else None)
            wireq, scales = self._quant_encode(flat, ef_key)
            qarr = self._stage_hier_rows(wireq)
            sarr = self._stage_hier_rows(scales)
            key = ("hier_allreduce", int(chunk), str(np_dtype), red_op,
                   float(prescale), float(postscale), k, codec.name)

            def _leg1(q, s):
                # Compressed reduce-scatter: exchange wire slices,
                # dequantize with per-sender scales, reduce in f32.
                y = q[0, 0].reshape(size, slice_n)
                w = jax.lax.all_to_all(y, "proc", split_axis=0,
                                       concat_axis=0)  # [size, slice_n]
                sg = jax.lax.all_gather(s[0, 0], "proc")   # [size, 1]
                deq = self._scaled(w.astype(jnp.float32) * sg, prescale)
                return self._scaled(
                    _axis0_reduce(deq, red_op, size), postscale)

            def _requant(rc):
                # ONE quantization definition for both legs: the same
                # jit-compatible quantizer that encoded leg 1 (1-D
                # input = one chunk), so the two legs can never drift
                # — and the fp8 path absmax-scales, never NaN-casting
                # a reduced value past e4m3's +-448 range.
                q2, ctx2 = self._quantizer.compress(rc)
                return q2, ctx2[0].astype(jnp.float32)

            def _leg2(q2, s2):
                # Compressed all-gather of the reduced slices, then
                # payload-dtype reassembly over in-host ICI.
                g = jax.lax.all_gather(q2, "proc")  # [size, slice_n]
                s2g = jax.lax.all_gather(s2.reshape(1), "proc")
                out = (g.astype(jnp.float32) * s2g).reshape(
                    chunk).astype(np_dtype)
                return jax.lax.all_gather(out, "local", tiled=True)

            if linear:
                def build():
                    def fn(q, s, res2):
                        rc = _leg1(q, s) + res2[0, 0]
                        q2, s2 = _requant(rc)
                        nres = rc - q2.astype(jnp.float32) * s2
                        return _leg2(q2, s2), nres[None, None]
                    return self._collective_jit(
                        fn, 3, (P(), P("proc", "local")),
                        mesh=self.mesh2, in_spec=P("proc", "local"))

                res2 = self._wire_residual2(ef_key, slice_n)
                out_g, nres = self._compiled(
                    key, build, (qarr, sarr, res2),
                    notify)(qarr, sarr, res2)
                self._store_residual2(ef_key, nres)
                out = self._replicated(out_g)
            else:
                def build():
                    def fn(q, s):
                        q2, s2 = _requant(_leg1(q, s))
                        return _leg2(q2, s2)
                    return self._collective_jit(
                        fn, 2, P(), mesh=self.mesh2,
                        in_spec=P("proc", "local"))

                out = self._replicated(
                    self._compiled(key, build, (qarr, sarr),
                                   notify)(qarr, sarr))
            return out[:int(n)] if padded > n else out
        # Cast codec (fp16/bf16): the staging pack casts to the wire
        # dtype, the cross-host reduce runs natively in it, and the
        # result returns to the payload dtype before the in-host
        # reassembly leg.
        stage_dtype = codec.wire if codec is not None else np_dtype
        garr = self._stage_hier([(p, 0, int(n))], int(n), chunk,
                                stage_dtype)

        key = ("hier_allreduce", int(chunk), str(np_dtype), red_op,
               float(prescale), float(postscale), k,
               codec.name if codec is not None else "none")

        def build():
            def fn(x):
                r = self._reduce_block(x[0, 0], red_op, prescale,
                                       postscale, self.size)
                if r.dtype != np_dtype:
                    r = r.astype(np_dtype)
                return jax.lax.all_gather(r, "local", tiled=True)
            return self._collective_jit(
                fn, 1, P(), mesh=self.mesh2, in_spec=P("proc", "local"))

        out = self._replicated(
            self._compiled(key, build, (garr,), notify)(garr))
        return out[:int(n)] if padded > n else out

    def _fused_allreduce_packed(self, payloads, lengths, dtype, red_op,
                                prescale, postscale,
                                notify=None):  # graftlint: hot-path
        """Multi-entry fusion via a bucket-padded flat buffer — the
        reference's fusion buffer (MemcpyInFusionBuffer / 64 MB
        persistent buffer, SURVEY §2.1 row 8) in XLA form.

        Group COMPOSITION depends on arrival timing: a DistributedOptimizer
        burst negotiates different (n_1..n_k) tuples cycle to cycle, and
        a compiled program per composition recompiles endlessly (measured
        16-60x slowdowns on async bursts).  Packing the entries into one
        size-class bucket keys the collective executable by bucket size
        alone; the pack/unpack copies are cheap eager device ops, exactly
        the memcpy in/out the reference pays."""
        np_dtype = np.dtype(dtype)
        total = int(sum(lengths))
        bucket = _size_class(total, np_dtype.itemsize)
        flat = self._pack_flat(
            [(p, 0, int(n)) for p, n in zip(payloads, lengths)],
            total, bucket, np_dtype)
        out = self.fused_allreduce([flat], [bucket], np_dtype, red_op,
                                   prescale, postscale, notify)[0]
        offs = np.concatenate([[0], np.cumsum(lengths)]).astype(int)  # graftlint: disable=host-bounce issue=ISSUE-1 -- offsets over negotiated lengths, never payload bytes
        return [out[offs[i]:offs[i] + lengths[i]]
                for i in range(len(lengths))]

    def allreduce(self, local_flat, red_op: str = SUM,
                  prescale: float = 1.0, postscale: float = 1.0):
        """Reduce one flat [n] contribution per process -> [n] device
        array (replicated on the mesh device)."""
        n = int(np.prod(np.shape(local_flat), dtype=np.int64))
        dtype = (local_flat.dtype if hasattr(local_flat, "dtype")
                 else np.asarray(local_flat).dtype)
        return self.fused_allreduce([local_flat], [n], dtype, red_op,
                                    prescale, postscale)[0]

    def broadcast(self, local, root_idx: int,
                  notify=None):  # graftlint: hot-path
        """Member ``root_idx``'s tensor to every process (masked psum:
        cheaper than an all-gather for size > 2, and explicit HLO).

        The program takes a power-of-two flat bucket, so a burst of
        varying shapes (``broadcast_parameters``: one op per layer)
        reuses one executable per size class instead of compiling per
        shape."""
        import jax
        import jax.numpy as jnp

        shape = tuple(np.shape(local))
        dtype = np.dtype(local.dtype if hasattr(local, "dtype")
                         else np.asarray(local).dtype)  # graftlint: disable=host-bounce issue=ISSUE-1 -- dtype probe; asarray branch reached only for host-typed inputs
        n = int(np.prod(shape, dtype=np.int64))
        # psum silently promotes bool to int32; ride the wire as uint8
        # and cast back so broadcast preserves every dtype.
        is_bool = dtype == np.bool_
        wire = np.dtype(np.uint8) if is_bool else dtype
        if is_bool:
            local = (local.astype(jnp.uint8) if _is_device_array(local)
                     else np.asarray(local).astype(np.uint8))  # graftlint: disable=host-bounce issue=ISSUE-1 -- bool wire-cast; np branch reached only for host-typed inputs
        bucket = _size_class(n, wire.itemsize)
        hier, codec_on = self._route("broadcast", n * wire.itemsize)
        codec = self._wire_codec(wire) if hier and codec_on else None

        def run_flat():
            key = ("broadcast", str(wire), int(bucket), int(root_idx))

            def build():
                def fn(x):
                    idx = jax.lax.axis_index("proc")
                    v = jnp.where(idx == root_idx, x[0],
                                  jnp.zeros_like(x[0]))
                    return jax.lax.psum(v, "proc")
                from jax.sharding import PartitionSpec as P
                return self._collective_jit(fn, 1, P())

            staged = self._stage_flat_padded([(local, 0, n)], n, bucket,
                                             wire)
            return self._replicated(
                self._compiled(key, build, (staged,), notify)(staged))

        if hier:
            _count_path("broadcast", n * wire.itemsize, True, codec,
                        self._wire_nbytes(codec, n) if codec else None)
            out = self._guarded(
                "broadcast", n * wire.itemsize,
                lambda: self._hier_broadcast(local, n, bucket, wire,
                                             root_idx, notify, codec),
                run_flat,
                payloads=((local,) if self.my_idx == root_idx else ()),
                codec=codec)
        else:
            _count_path("broadcast", n * wire.itemsize, False)
            out = run_flat()
        out = (out[:n].reshape(shape) if out.shape[0] > n
               else out.reshape(shape))
        return out.astype(jnp.bool_) if is_bool else out

    def _hier_broadcast(self, p, n: int, bucket: int, wire, root_idx,
                        notify=None, codec=None):  # graftlint: hot-path
        """Broadcast over the proc x local mesh: the root's payload
        scatters into k chunks across its local chips (staging), each
        chunk rides a masked cross-host psum over that chip's own
        ICI/DCN links (1/k of the bytes per chip), and a local
        ``all_gather`` reassembles the full tensor on every chip —
        the ``_hier_allreduce`` treatment for the one-sender case
        (``broadcast_parameters`` sweeps are burst of exactly these).
        Non-root members stage zeros (nothing of theirs is sent), and
        the in-program root mask stays as defense in depth."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        k = self.local_size
        chunk = -(-int(bucket) // k)
        segments = ([(p, 0, int(n))] if self.my_idx == root_idx else [])
        if codec is not None and codec.kind == "quant":
            # Data-movement op: plain quantize/dequantize (no error
            # feedback — nothing is reduced, the error never
            # compounds).  The root's quantized payload rides a masked
            # psum over the wire BYTES (bitcast to u8: non-roots
            # contribute exact zeros, so the byte sum IS the root's
            # wire — no quantized arithmetic, any 1-byte wire dtype,
            # and the uncompressed broadcast's ~2*(p-1)/p movement
            # shape at 1/4 the byte width — never an O(p) full-wire
            # all_gather).  The root's scale rides the same masked
            # psum.
            np_pay = np.dtype(wire)
            wire_jnp = codec.wire
            if self.my_idx == root_idx:
                flat = self._pack_flat(segments, int(n), chunk * k,
                                       np_pay)
                wireq, scales = self._quant_encode(flat)
            else:
                # Non-roots contribute nothing: stage zero wire rows
                # and unit scales directly instead of paying a full
                # quantization pass over a zero buffer the in-program
                # root mask discards anyway.
                with jax.default_device(self.device):
                    wireq = jnp.zeros((k, chunk), wire_jnp)
                    scales = jnp.ones((k, 1), jnp.float32)
            qarr = self._stage_hier_rows(wireq)
            sarr = self._stage_hier_rows(scales)
            key = ("hier_broadcast", str(wire), int(chunk),
                   int(root_idx), k, codec.name)

            def build():
                def fn(q, s):
                    idx = jax.lax.axis_index("proc")
                    qv = jnp.where(idx == root_idx, q[0, 0],
                                   jnp.zeros_like(q[0, 0]))
                    sv = jnp.where(idx == root_idx, s[0, 0],
                                   jnp.zeros_like(s[0, 0]))
                    qb = jax.lax.psum(jax.lax.bitcast_convert_type(
                        qv, jnp.uint8), "proc")
                    qr = jax.lax.bitcast_convert_type(qb, wire_jnp)
                    sr = jax.lax.psum(sv, "proc")      # [1] f32
                    deq = (qr.astype(jnp.float32) * sr).astype(np_pay)
                    return jax.lax.all_gather(deq, "local", tiled=True)
                return self._collective_jit(fn, 2, P(), mesh=self.mesh2,
                                            in_spec=P("proc", "local"))

            return self._replicated(
                self._compiled(key, build, (qarr, sarr),
                               notify)(qarr, sarr))
        stage_dtype = codec.wire if codec is not None else wire
        key = ("hier_broadcast", str(wire), int(chunk), int(root_idx), k,
               codec.name if codec is not None else "none")

        def build():
            def fn(x):
                idx = jax.lax.axis_index("proc")
                v = jnp.where(idx == root_idx, x[0, 0],
                              jnp.zeros_like(x[0, 0]))
                r = jax.lax.psum(v, "proc")
                if r.dtype != np.dtype(wire):
                    r = r.astype(wire)
                return jax.lax.all_gather(r, "local", tiled=True)
            return self._collective_jit(fn, 1, P(), mesh=self.mesh2,
                                        in_spec=P("proc", "local"))

        garr = self._stage_hier(
            segments, int(n) if segments else 0, chunk, stage_dtype)
        return self._replicated(
            self._compiled(key, build, (garr,), notify)(garr))

    def allgather(self, local, rows_per_member: Sequence[int],
                  notify=None):  # graftlint: hot-path
        """Concat dim-0-ragged per-process tensors (reference
        AllgatherOp): each member's contribution flattens into a
        power-of-two bucket, one ``lax.all_gather`` moves the buckets,
        and the valid segments are sliced back out eagerly.  The
        executable is keyed by (dtype, bucket) ALONE, so ragged bursts
        whose row counts vary call to call (variable-length batches,
        ``allgather_object``) reuse one program per size class —
        the ``_fused_allreduce_packed`` recompile-cliff treatment."""
        import jax
        import jax.numpy as jnp

        rows = [int(r) for r in rows_per_member]
        trailing = tuple(np.shape(local))[1:]
        telems = int(np.prod(trailing, dtype=np.int64)) if trailing else 1
        dtype = np.dtype(local.dtype if hasattr(local, "dtype")
                         else np.asarray(local).dtype)  # graftlint: disable=host-bounce issue=ISSUE-1 -- dtype probe; asarray branch reached only for host-typed inputs
        lens = [r * telems for r in rows]
        if not lens or max(lens) == 0:
            with jax.default_device(self.device):
                return jnp.zeros((0,) + trailing, dtype)
        bucket = _size_class(max(lens), dtype.itemsize)
        size = self.size
        my_len = lens[self.my_idx]
        hier, codec_on = self._route("allgather", bucket * dtype.itemsize)
        codec = self._wire_codec(dtype) if hier and codec_on else None

        def run_flat():
            key = ("allgather", str(dtype), int(bucket))

            def build():
                def fn(x):
                    return jax.lax.all_gather(x[0], "proc")  # [size, bucket]
                from jax.sharding import PartitionSpec as P
                return self._collective_jit(fn, 1, P())

            staged = self._stage_flat_padded([(local, 0, my_len)],
                                             my_len, bucket, dtype)
            return self._replicated(
                self._compiled(key, build, (staged,), notify)(staged))

        if hier:
            _count_path("allgather", my_len * dtype.itemsize, True,
                        codec,
                        self._wire_nbytes(codec, my_len)
                        if codec else None)
            # Both planes' outputs slice identically: flat g is
            # [size, bucket], hier g is [size, k*chunk >= bucket], and
            # the valid-segment slice below reads lens[m] <= bucket
            # rows either way — so a degraded fallback is transparent.
            g = self._guarded(
                "allgather", bucket * dtype.itemsize,
                lambda: self._hier_allgather(local, my_len, bucket,
                                             dtype, notify, codec),
                run_flat, payloads=(local,), codec=codec)
        else:
            _count_path("allgather", my_len * dtype.itemsize, False)
            g = run_flat()
        parts = [g[m, :lens[m]].reshape((rows[m],) + trailing)
                 for m in range(size) if rows[m]]
        return (jnp.concatenate(parts, axis=0) if len(parts) > 1
                else parts[0])

    def _hier_allgather(self, p, my_len: int, bucket: int, np_dtype,
                        notify=None, codec=None):  # graftlint: hot-path
        """Allgather over the proc x local mesh: each member's padded
        bucket splits into k chunks across its local chips; chunk j
        all_gathers over the ``proc`` axis from local device j (every
        chip moves (size-1)/k buckets cross-host instead of chip 0
        moving them all), and a local ``all_gather`` reassembles the
        member-major [size, bucket] result over intra-host ICI.
        Returns the gathered [size, k*ceil(bucket/k)] device array
        (k*chunk >= bucket; callers slice valid rows)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        k = self.local_size
        chunk = -(-int(bucket) // k)
        size = self.size
        if codec is not None and codec.kind == "quant":
            # Data-movement op: plain quantize/dequantize.  The
            # cross-host all_gather moves the WIRE payload (+ one f32
            # scale per chunk); each member's rows dequantize with its
            # own scale before the in-host reassembly leg.
            np_d = np.dtype(np_dtype)
            flat = self._pack_flat([(p, 0, int(my_len))], int(my_len),
                                   chunk * k, np_d)
            wireq, scales = self._quant_encode(flat)
            qarr = self._stage_hier_rows(wireq)
            sarr = self._stage_hier_rows(scales)
            key = ("hier_allgather", str(np_dtype), int(chunk), k,
                   codec.name)

            def build():
                def fn(q, s):
                    g = jax.lax.all_gather(q[0, 0], "proc")   # [p,chunk]
                    sg = jax.lax.all_gather(s[0, 0], "proc")  # [p,1]
                    deq = (g.astype(jnp.float32) * sg).astype(np_d)
                    gg = jax.lax.all_gather(deq, "local")  # [k,p,chunk]
                    return jnp.swapaxes(gg, 0, 1).reshape(
                        size, k * chunk)
                return self._collective_jit(fn, 2, P(), mesh=self.mesh2,
                                            in_spec=P("proc", "local"))

            return self._replicated(
                self._compiled(key, build, (qarr, sarr),
                               notify)(qarr, sarr))
        stage_dtype = codec.wire if codec is not None else np_dtype
        key = ("hier_allgather", str(np_dtype), int(chunk), k,
               codec.name if codec is not None else "none")

        def build():
            def fn(x):
                g = jax.lax.all_gather(x[0, 0], "proc")  # [size, chunk]
                if g.dtype != np.dtype(np_dtype):
                    g = g.astype(np_dtype)
                gg = jax.lax.all_gather(g, "local")      # [k, size, chunk]
                return jnp.swapaxes(gg, 0, 1).reshape(size, k * chunk)
            return self._collective_jit(fn, 1, P(), mesh=self.mesh2,
                                        in_spec=P("proc", "local"))

        garr = self._stage_hier([(p, 0, int(my_len))], int(my_len),
                                chunk, stage_dtype)
        return self._replicated(
            self._compiled(key, build, (garr,), notify)(garr))

    def alltoall(self, local, splits_matrix: np.ndarray,
                 notify=None):  # graftlint: hot-path
        """Member-major splits matrix routing (reference AlltoallOp) as
        real ``lax.all_to_all`` HLO: each send segment is padded to the
        matrix max so every exchange block is uniform, one all-to-all
        moves them, and the receiver slices its valid rows back out.
        Returns (my_received_rows, recv_splits).
        """
        import jax
        import jax.numpy as jnp

        sm = np.asarray(splits_matrix).reshape(self.size, self.size)  # graftlint: disable=host-bounce issue=ISSUE-1 -- negotiated splits matrix (control metadata), never payload bytes
        trailing = tuple(np.shape(local))[1:]
        telems = int(np.prod(trailing, dtype=np.int64)) if trailing else 1
        dtype = np.dtype(local.dtype if hasattr(local, "dtype")
                         else np.asarray(local).dtype)  # graftlint: disable=host-bounce issue=ISSUE-1 -- dtype probe; asarray branch reached only for host-typed inputs
        size = self.size
        c = int(sm.max()) if sm.size else 0
        recv_splits = [int(sm[j, self.my_idx]) for j in range(size)]
        if c == 0:
            with jax.default_device(self.device):
                return jnp.zeros((0,) + trailing, dtype), recv_splits
        # Every exchange block pads to one power-of-two bucket derived
        # from the NEGOTIATED matrix max (identical on all members), so
        # the executable is keyed by (dtype, block) alone — varying
        # splits matrices (MoE routing shifts every step) reuse one
        # program per size class instead of compiling per matrix.
        block = _size_class(c * telems, dtype.itemsize)
        my_idx = self.my_idx
        offs = np.concatenate([[0], np.cumsum(sm[my_idx])]).astype(int)  # graftlint: disable=host-bounce issue=ISSUE-1 -- offsets over the negotiated splits row, never payload bytes

        hier, codec_on = self._route("alltoall",
                                     size * block * dtype.itemsize)
        codec = self._wire_codec(dtype) if hier and codec_on else None

        def run_flat():
            key = ("alltoall", str(dtype), int(block))

            def build():
                def fn(x):
                    y = x[0].reshape(size, block)
                    w = jax.lax.all_to_all(y, "proc", split_axis=0,
                                           concat_axis=0)  # [size, block]
                    return w.reshape(1, size * block)
                from jax.sharding import PartitionSpec as P
                return self._collective_jit(fn, 1, P("proc"))

            # Segment layout: dest j's rows (slice from my payload),
            # padded to the uniform block.
            segments = []
            for j in range(size):
                seg_elems = int(sm[my_idx, j]) * telems
                segments.append((local, int(offs[j]) * telems,
                                 seg_elems))
                if seg_elems < block:
                    segments.append((None, 0, block - seg_elems))
            staged = self._stage_flat_padded(segments, size * block,
                                             size * block, dtype)
            return self._my_row(
                self._compiled(key, build, (staged,), notify)(staged)), block

        if hier:
            _count_path("alltoall",
                        int(offs[-1]) * telems * dtype.itemsize, True,
                        codec,
                        self._wire_nbytes(codec, int(offs[-1]) * telems)
                        if codec else None)
            # stride differs per plane (flat = block, hier = k*ceil),
            # so each closure returns its own (row, stride) pair and
            # the valid-rows slice below works either way.
            w, stride = self._guarded(
                "alltoall", size * block * dtype.itemsize,
                lambda: self._hier_alltoall(local, sm, offs, telems,
                                            block, dtype, notify,
                                            codec),
                run_flat, payloads=(local,), codec=codec)
        else:
            _count_path("alltoall",
                        int(offs[-1]) * telems * dtype.itemsize, False)
            w, stride = run_flat()
        parts = [w[j * stride:j * stride + recv_splits[j] * telems]
                 .reshape((recv_splits[j],) + trailing)
                 for j in range(size) if recv_splits[j]]
        if not parts:
            with jax.default_device(self.device):
                return jnp.zeros((0,) + trailing, dtype), recv_splits
        out = (jnp.concatenate(parts, axis=0) if len(parts) > 1
               else parts[0])
        return out, recv_splits

    def _hier_alltoall(self, p, sm, offs, telems: int, block: int,
                       np_dtype, notify=None,
                       codec=None):  # graftlint: hot-path
        """Alltoall over the proc x local mesh: every destination block
        splits into k chunks across the local chips; local device j
        runs the cross-host ``all_to_all`` for chunk j of every block
        (each chip exchanges 1/k of the bytes over its own links), and
        a local ``all_gather`` reassembles the received blocks.
        Returns (my received flat [size * k*ceil(block/k)] row, the
        per-source stride k*ceil(block/k))."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        k = self.local_size
        bc = -(-int(block) // k)    # block chunk per local chip
        blockk = bc * k
        size = self.size
        my_idx = self.my_idx
        segments = _chunked_segments(
            p, size, [int(offs[m]) * telems for m in range(size)],
            [int(sm[my_idx, m]) * telems for m in range(size)], bc, k)
        if codec is not None and codec.kind == "quant":
            # Data-movement op: plain quantize/dequantize.  The
            # cross-host all_to_all exchanges the WIRE payload; each
            # received row m dequantizes with sender m's this-chunk
            # scale (one scalar all_gather rides along) before the
            # in-host reassembly leg.
            np_d = np.dtype(np_dtype)
            flat = self._pack_flat(segments, size * blockk,
                                   size * bc * k, np_d)
            wireq, scales = self._quant_encode(flat)
            qarr = self._stage_hier_rows(wireq)
            sarr = self._stage_hier_rows(scales)
            key = ("hier_alltoall", str(np_dtype), int(bc), k,
                   codec.name)

            def build():
                def fn(q, s):
                    y = q[0, 0].reshape(size, bc)
                    w = jax.lax.all_to_all(y, "proc", split_axis=0,
                                           concat_axis=0)  # [size, bc]
                    sg = jax.lax.all_gather(s[0, 0], "proc")  # [size,1]
                    deq = (w.astype(jnp.float32) * sg).astype(np_d)
                    ww = jax.lax.all_gather(deq, "local")  # [k,size,bc]
                    return jnp.swapaxes(ww, 0, 1).reshape(
                        1, size * blockk)
                return self._collective_jit(fn, 2, P("proc"),
                                            mesh=self.mesh2,
                                            in_spec=P("proc", "local"))

            w = self._my_row(
                self._compiled(key, build, (qarr, sarr),
                               notify)(qarr, sarr))
            return w, blockk
        stage_dtype = codec.wire if codec is not None else np_dtype
        key = ("hier_alltoall", str(np_dtype), int(bc), k,
               codec.name if codec is not None else "none")

        def build():
            def fn(x):
                y = x[0, 0].reshape(size, bc)
                w = jax.lax.all_to_all(y, "proc", split_axis=0,
                                       concat_axis=0)   # [size, bc]
                if w.dtype != np.dtype(np_dtype):
                    w = w.astype(np_dtype)
                ww = jax.lax.all_gather(w, "local")     # [k, size, bc]
                return jnp.swapaxes(ww, 0, 1).reshape(
                    1, size * blockk)
            return self._collective_jit(fn, 1, P("proc"),
                                        mesh=self.mesh2,
                                        in_spec=P("proc", "local"))

        garr = self._stage_hier(segments, size * blockk, size * bc,
                                stage_dtype)
        w = self._my_row(
            self._compiled(key, build, (garr,), notify)(garr))
        return w, blockk

    def reducescatter(self, local, red_op: str = SUM, notify=None,
                      name=None):  # graftlint: hot-path
        """Reduce then scatter dim-0 shards as real ``psum_scatter``
        HLO (uneven chunks follow the reference's earlier-ranks-larger
        split: each chunk is padded to the largest inside the program,
        scattered tiled, and sliced back out)."""
        import jax
        import jax.numpy as jnp

        shape = tuple(np.shape(local))
        dtype = np.dtype(local.dtype if hasattr(local, "dtype")
                         else np.asarray(local).dtype)  # graftlint: disable=host-bounce issue=ISSUE-1 -- dtype probe; asarray branch reached only for host-typed inputs
        d0 = shape[0]
        trailing = shape[1:]
        telems = int(np.prod(trailing, dtype=np.int64)) if trailing else 1
        size = self.size
        rows, offs = _uneven_chunks(d0, size)
        c = rows[0] if rows else 0  # largest chunk (earlier ranks larger)
        # Member-major packed buffer: member m's chunk flattens and
        # pads to one power-of-two segment, so the executable is keyed
        # by (dtype, segment, op) — shape-varying bursts reuse one
        # program per size class (the packed-fusion-bucket treatment).
        seg = _size_class(max(c * telems, 1), dtype.itemsize)
        my_idx = self.my_idx
        hier = codec_on = False
        if red_op in (SUM, AVERAGE, MIN, MAX, PRODUCT):
            hier, codec_on = self._route("reducescatter",
                                         size * seg * dtype.itemsize)
        codec = (self._wire_codec(dtype, red_op) if hier and codec_on
                 else None)
        my_n = rows[my_idx] * telems

        def run_flat():
            key = ("reducescatter", str(dtype), int(seg), red_op)

            def build():
                def fn(x):
                    y = x[0]  # [size*seg]
                    if red_op in (SUM, AVERAGE):
                        w = jax.lax.psum_scatter(
                            y, "proc", scatter_dimension=0, tiled=True)
                        if red_op == AVERAGE:
                            # Divides by the full member count (core
                            # reducescatter semantics; join cannot reach
                            # this op).
                            w = (w / size).astype(w.dtype) if \
                                jnp.issubdtype(w.dtype, jnp.floating) \
                                else w // size
                    elif red_op in (MIN, MAX, PRODUCT):
                        # One all_to_all + local reduce: 1x payload bytes
                        # (the full-reduce-then-slice fallback moved N x).
                        w = alltoall_chunk_reduce(y, "proc", size, red_op)
                    else:
                        r = self._reduce_block(y, red_op, 1.0, 1.0, size)
                        w = jax.lax.slice_in_dim(
                            r, my_idx * seg, (my_idx + 1) * seg)
                    return w[None]  # [1, seg]
                from jax.sharding import PartitionSpec as P
                return self._collective_jit(fn, 1, P("proc"))

            segments = []
            for m in range(size):
                n_m = rows[m] * telems
                segments.append((local, int(offs[m]) * telems, n_m))
                if n_m < seg:
                    segments.append((None, 0, seg - n_m))
            staged = self._stage_flat_padded(segments, size * seg,
                                             size * seg, dtype)
            out = self._my_row(
                self._compiled(key, build, (staged,), notify)(staged))
            return out[:my_n].reshape((rows[my_idx],) + trailing)

        if hier:
            # Adasum (and any other whole-vector combine) stays on the
            # one-device plane: per-chunk combines would change the
            # math — the ``_hier_allreduce`` exclusion.
            _count_path("reducescatter", d0 * telems * dtype.itemsize,
                        True, codec,
                        self._wire_nbytes(codec, d0 * telems)
                        if codec else None)

            def run_hier():
                out = self._hier_reducescatter(local, rows, offs,
                                               telems, seg, dtype,
                                               red_op, notify, codec,
                                               name)
                return out[:my_n].reshape((rows[my_idx],) + trailing)

            return self._guarded("reducescatter",
                                 size * seg * dtype.itemsize, run_hier,
                                 run_flat, payloads=(local,),
                                 codec=codec)
        _count_path("reducescatter", d0 * telems * dtype.itemsize,
                    False)
        return run_flat()

    def _hier_reducescatter(self, p, rows, offs, telems: int, seg: int,
                            np_dtype, red_op, notify=None, codec=None,
                            ef_name=None):  # graftlint: hot-path
        """Reducescatter over the proc x local mesh: every member
        segment splits into k chunks across the local chips; local
        device j reduces+scatters chunk j of every segment over the
        ``proc`` axis (``psum_scatter`` for Sum/Average, the
        bytes-proportional ``alltoall_chunk_reduce`` for
        Min/Max/Product — each chip moving 1/k of the bytes), and a
        local ``all_gather`` reassembles this member's full reduced
        segment.  Returns the flat padded [k*ceil(seg/k)] row."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        k = self.local_size
        sc = -(-int(seg) // k)      # segment chunk per local chip
        size = self.size
        segments = _chunked_segments(
            p, size, [int(offs[m]) * telems for m in range(size)],
            [int(rows[m]) * telems for m in range(size)], sc, k)
        if codec is not None and codec.kind == "quant":
            # Only the cross-host REDUCE leg is compressed: the
            # quantized member segments exchange via all_to_all (the
            # reduce-scatter's wire movement), dequantize to f32 with
            # per-sender scales, and reduce locally; the in-host
            # reassembly all_gather stays in the payload dtype.
            # Error feedback for the linear ops, plain otherwise.
            np_d = np.dtype(np_dtype)
            flat = self._pack_flat(segments, size * sc * k,
                                   size * sc * k, np_d)
            ef_key = (("reducescatter", size * sc * k, str(np_d),
                       ef_name)
                      if red_op in (SUM, AVERAGE) else None)
            wireq, scales = self._quant_encode(flat, ef_key)
            qarr = self._stage_hier_rows(wireq)
            sarr = self._stage_hier_rows(scales)
            key = ("hier_reducescatter", str(np_dtype), int(sc), red_op,
                   k, codec.name)

            def build():
                def fn(q, s):
                    y = q[0, 0].reshape(size, sc)
                    w = jax.lax.all_to_all(y, "proc", split_axis=0,
                                           concat_axis=0)  # [size, sc]
                    sg = jax.lax.all_gather(s[0, 0], "proc")  # [size,1]
                    deq = w.astype(jnp.float32) * sg
                    r = _axis0_reduce(deq, red_op, size).astype(np_d)
                    return jax.lax.all_gather(
                        r, "local", tiled=True)[None]
                return self._collective_jit(fn, 2, P("proc"),
                                            mesh=self.mesh2,
                                            in_spec=P("proc", "local"))

            return self._my_row(
                self._compiled(key, build, (qarr, sarr),
                               notify)(qarr, sarr))
        stage_dtype = codec.wire if codec is not None else np_dtype
        key = ("hier_reducescatter", str(np_dtype), int(sc), red_op, k,
               codec.name if codec is not None else "none")

        def build():
            def fn(x):
                y = x[0, 0]          # [size * sc]
                if red_op in (SUM, AVERAGE):
                    w = jax.lax.psum_scatter(
                        y, "proc", scatter_dimension=0, tiled=True)
                    if red_op == AVERAGE:
                        w = (w / size).astype(w.dtype) if \
                            jnp.issubdtype(w.dtype, jnp.floating) \
                            else w // size
                else:
                    w = alltoall_chunk_reduce(y, "proc", size, red_op)
                if w.dtype != np.dtype(np_dtype):
                    w = w.astype(np_dtype)
                return jax.lax.all_gather(w, "local", tiled=True)[None]
            return self._collective_jit(fn, 1, P("proc"),
                                        mesh=self.mesh2,
                                        in_spec=P("proc", "local"))

        garr = self._stage_hier(segments, size * sc * k, size * sc,
                                stage_dtype)
        return self._my_row(
            self._compiled(key, build, (garr,), notify)(garr))


class MultihostEngine:
    """Single executor thread draining the core's negotiated groups.

    Enqueue side: ops are registered with the control plane
    (``TcpCore.enqueue_external``) and the local payload parked here.
    Executor side: for each negotiated group (one fused Response), run
    the XLA collective over the global mesh in negotiation order, then
    complete both the Python handles and the core entries.
    """

    def __init__(self, core, config: Config, timeline: Timeline,
                 process_set_resolver):
        self.core = core
        self.config = config
        self.timeline = timeline
        self._resolve_process_set = process_set_resolver
        # Process-set mesh memo: reached from the caller plane
        # (enqueue_alltoall sizing) and the executor thread.
        self._collectives: Dict[int, GlobalMeshCollectives] = {}  # graftlint: guarded-by=_lock
        self._lock = threading.Lock()
        # core handle -> (py handle, local payload ndarray, orig shape)
        self._pending: Dict[int, tuple] = {}  # graftlint: guarded-by=_lock
        # Monotonic False->True poison flag, read racily by the drain /
        # watchdog loops as their while-predicate (GIL-atomic; a late
        # read costs one extra bounded wait, never a hang).
        self._shutdown = False  # graftlint: owned-by=any
        # Two-stage pipeline (the reference's background loop negotiates
        # cycle N+1 while N's NCCL kernels run async, SURVEY §3.2): the
        # drain thread only stages + dispatches compiled programs (XLA
        # dispatch is async), the completion thread performs the
        # blocking device_get / handle resolution.  Bounded so a slow
        # host fetch backpressures dispatch instead of piling device
        # programs without limit.
        # Pipeline depth: device programs dispatched but not yet
        # complete.  The drain thread parks one representative output
        # per group and blocks on the OLDEST once the window fills —
        # bounding live staging/output buffers (the reference's finite
        # NCCL stream queue) while keeping up to `depth` collectives
        # overlapped on device.  Only the drain thread touches it.
        self._depth = max(1, int(getattr(config, "max_inflight_groups",
                                         4)))
        self._inflight_outs: List = []  # graftlint: owned-by=hvd-tpu-multihost-exec
        self._done_q: "queue_mod.Queue" = queue_mod.Queue(
            maxsize=self._depth)
        # Groups routed through the completion thread and not yet
        # finished (guarded by _lock): the drain thread completes a
        # device-only group inline ONLY when this is zero, so handle
        # resolution order always follows negotiation order.
        self._host_inflight = 0  # graftlint: guarded-by=_lock
        # Execution-phase watchdog (the device-plane analog of the
        # stall inspector): dispatched groups register here; a group
        # that outlives stall_warning_secs logs a warning, and — when
        # device_exec_timeout_secs > 0 — one that outlives the timeout
        # fails every outstanding handle with a diagnostic naming the
        # group, then poisons the engine (a member that died after
        # negotiation leaves the runtime wedged; callers must not hang
        # with it).
        # Monotonic collective-group id (mirrors the in-process
        # engine's): tags each negotiated group's timeline EXEC span
        # and the engine_last_group_id gauge for trace<->metrics
        # correlation.
        self._group_seq = 0  # graftlint: owned-by=hvd-tpu-multihost-exec
        # -- steady-state fast path (frozen negotiated schedules) ----------
        # Caller threads stage payloads against the frozen schedule and
        # hand full buckets to the drain thread via _fp_q, so every
        # dispatch still flows through _execute (one schedule entry, one
        # watchdog/deadline registration path).  _fp_lock is the
        # freezer's stage lock and is ALWAYS taken before self._lock
        # (the thaw flush re-enqueues through the core under both).
        self._fp_lock = threading.RLock()
        # Staged-but-undispatched payloads of the CURRENT bucket only:
        # (py handle, ndarray, name) in frozen slot order.  A thaw
        # flush renegotiates exactly these — already-dispatched buckets
        # are in flight and complete through _finish.
        self._fp_pending: List[tuple] = []  # graftlint: guarded-by=_fp_lock
        self._fp_idx = 0  # graftlint: guarded-by=_fp_lock
        self._fp_t = 0.0  # graftlint: guarded-by=_fp_lock
        # Synthetic frozen-bucket groups, drained by the exec thread
        # ahead of negotiated records (queue is thread-safe; unbounded
        # is fine — depth is capped by the frozen schedule's bucket
        # count times the caller's own blocking cadence).
        self._fp_q: "queue_mod.Queue" = queue_mod.Queue()
        self._fp = fastpath.ScheduleFreezer(
            warm_cycles=config.fast_path_warm_cycles,
            enabled=getattr(config, "fast_path", True), spmd=True,
            plane_name="multihost", on_thaw=self._fp_flush,
            stage_lock=self._fp_lock)
        fastpath.register(self._fp)
        fastpath.set_core_rounds_provider(core.fastpath_idle_rounds)
        self._m_fp_frozen = metrics.counter("fastpath_frozen_cycles_total")
        self._m_fp_bucket = metrics.histogram(
            "engine_overlap_bucket_seconds")
        # Fixed unlabeled series resolved once (hot-path discipline);
        # the exec-cache gauges additionally refresh at most 1/s —
        # they only change on a compile, and _finish runs per group.
        self._m_cycles = metrics.counter("engine_cycles_total")
        self._m_queue_depth = metrics.gauge("engine_queue_depth")
        self._m_bytes_submitted = metrics.counter(
            "engine_bytes_submitted_total")
        self._m_bytes_fused = metrics.counter("engine_bytes_fused_total")
        self._m_tensors_fused = metrics.counter(
            "engine_tensors_fused_total")
        self._m_cache_hits = metrics.gauge("exec_cache_hits")
        self._m_cache_misses = metrics.gauge("exec_cache_misses")
        self._m_last_group = metrics.gauge("engine_last_group_id")
        # Read/written racily from the drain AND completion threads as
        # a refresh throttle; a lost update costs one extra gauge
        # refresh, never a wrong value.
        self._cache_gauge_t = 0.0  # graftlint: owned-by=any
        self._watch_lock = threading.Lock()
        self._watched: Dict[int, dict] = {}  # graftlint: guarded-by=_watch_lock
        self._killed_wids: set = set()  # graftlint: guarded-by=_watch_lock
        self._watch_seq = 0  # graftlint: guarded-by=_watch_lock
        self._last_progress = time.monotonic()  # graftlint: guarded-by=_watch_lock
        # Set under _lock so the poison is atomic with the pending-map
        # sweep; read racily as a fast-path check (reads unchecked).
        self._failed: Optional[Exception] = None  # graftlint: guarded-by=_lock
        # HOROVOD_STALL_CHECK_DISABLE silences the warning path here
        # exactly like the negotiation-phase inspector; the explicit
        # timeout knob remains a separate opt-in.
        self._exec_warn = (0.0 if getattr(config, "stall_check_disable",
                                          False)
                           else max(float(config.stall_warning_secs),
                                    0.0))
        self._exec_timeout = max(float(getattr(
            config, "device_exec_timeout_secs", 0.0)), 0.0)
        # Per-collective deadlines ride the same watchdog thread: when
        # the deadline plane is on, the thread must run even with the
        # warning/timeout knobs off.
        self._deadline_enabled = resilience.collective_timeout_secs() > 0
        if (self._exec_warn > 0 or self._exec_timeout > 0
                or self._deadline_enabled):
            self._watchdog = threading.Thread(
                target=self._watchdog_loop,
                name="hvd-tpu-multihost-watchdog", daemon=True)
            self._watchdog.start()
        self._done_thread = threading.Thread(
            target=self._completion_loop,
            name="hvd-tpu-multihost-done", daemon=True)
        self._done_thread.start()
        self._thread = threading.Thread(
            target=self._loop, name="hvd-tpu-multihost-exec", daemon=True)
        self._thread.start()

    # -- process-set meshes ------------------------------------------------

    def collectives_for(self, process_set_id: int) -> GlobalMeshCollectives:
        # Reached from the caller plane (enqueue_alltoall sizing) AND
        # the executor thread (_execute): memoize under the lock so two
        # racing first-touches can't build two global meshes (and two
        # compiled-program caches) for one set.
        with self._lock:
            mc = self._collectives.get(process_set_id)
            if mc is None:
                ranks = self._resolve_process_set(process_set_id)
                mc = GlobalMeshCollectives(
                    ranks, name="ps%d" % process_set_id)
                self._collectives[process_set_id] = mc
            return mc

    def invalidate_process_set(self, process_set_id: int):
        # Membership changed: a frozen schedule negotiated against the
        # old mesh must never dispatch again (loud thaw, before _lock —
        # the flush path takes _fp_lock then _lock).
        self._fp.thaw("membership",
                      detail="process set %d invalidated" % process_set_id)
        with self._lock:
            self._collectives.pop(process_set_id, None)

    # -- enqueue API (per-rank tensor semantics) ---------------------------

    @staticmethod
    def _payload(tensor):
        """Keep device arrays device-resident; host data becomes one
        contiguous numpy array (crossing the host boundary is then the
        caller's choice, never this engine's)."""
        if _is_device_array(tensor):
            return tensor
        return np.ascontiguousarray(np.asarray(tensor))

    def _enqueue(self, name, op_type, arr, **kw) -> CollectiveHandle:
        fp = self._fp_stage(name, op_type, arr, kw)
        if fp is not None:
            return fp
        py = CollectiveHandle(name)
        # Enqueue and park ATOMICALLY w.r.t. the executor's _take: the
        # instant enqueue_external returns, the background thread can
        # negotiate the op and the executor can pop its record — if the
        # payload weren't parked yet, this rank would contribute zeros
        # and the handle would never resolve.  The _failed check lives
        # under the same lock the watchdog uses for its pending sweep,
        # so a handle either raises here or is guaranteed to be swept.
        with self._lock:
            if self._failed is not None:
                raise HorovodInternalError(
                    "multihost engine disabled after watchdog "
                    "failure: %s" % self._failed)
            faultline.site("mh.enqueue.pre_register")
            ch = self.core.enqueue_external(
                name, op_type, tuple(arr.shape), np.dtype(arr.dtype),
                **kw)
            self._pending[ch._h] = (py, arr)
            self._m_bytes_submitted.inc(int(arr.nbytes))
            self._m_queue_depth.set(len(self._pending))
        return py

    def enqueue_allreduce(self, name, tensor, red_op=SUM, prescale=1.0,
                          postscale=1.0, process_set_id=0
                          ) -> CollectiveHandle:
        return self._enqueue(
            name, "allreduce", self._payload(tensor), red_op=red_op,
            process_set_id=process_set_id, prescale=prescale,
            postscale=postscale)

    def enqueue_allgather(self, name, tensor, process_set_id=0
                          ) -> CollectiveHandle:
        return self._enqueue(name, "allgather", self._payload(tensor),
                             process_set_id=process_set_id)

    def enqueue_broadcast(self, name, tensor, root_rank=0,
                          process_set_id=0) -> CollectiveHandle:
        return self._enqueue(name, "broadcast", self._payload(tensor),
                             root_rank=root_rank,
                             process_set_id=process_set_id)

    def enqueue_alltoall(self, name, tensor, splits=None,
                         process_set_id=0) -> CollectiveHandle:
        arr = self._payload(tensor)
        if splits is None:
            n = self.collectives_for(process_set_id).size
            if arr.shape[0] % n:
                raise ValueError(
                    "uniform alltoall needs dim0 %% set size (%d) == 0"
                    % n)
            splits = [arr.shape[0] // n] * n
        return self._enqueue(name, "alltoall", arr, splits=list(splits),
                             process_set_id=process_set_id)

    def enqueue_reducescatter(self, name, tensor, red_op=SUM,
                              process_set_id=0) -> CollectiveHandle:
        return self._enqueue(name, "reducescatter", self._payload(tensor),
                             red_op=red_op,
                             process_set_id=process_set_id)

    # -- steady-state fast path (frozen negotiated schedules) --------------

    @staticmethod
    def _fp_slot_sig(op_type, arr, kw) -> tuple:
        """Positional slot identity on the enqueue side.  Names carry
        step suffixes in real training loops, so frozen slots match on
        what negotiation actually keys on — op, set, dtype, reduction
        parameters and flat size at position i (the upstream
        ``response_cache.cc`` keys on shape/type for the same reason)."""
        return (op_type, int(kw.get("process_set_id", 0)),
                np.dtype(arr.dtype).name, kw.get("red_op"),
                float(kw.get("prescale", 1.0)),
                float(kw.get("postscale", 1.0)), int(arr.size))

    def _fp_profile(self, g: dict):
        """One negotiated record's schedule profile, or None when the
        record is not freezable (non-allreduce, error record, or a
        zero-filled joined entry — membership is mid-change)."""
        if (g["op_type"] != "allreduce" or g.get("error")
                or any(e["handle"] < 0 for e in g["entries"])):
            return None
        dtype = np.dtype(g["dtype"]).name
        return tuple(
            ("allreduce", int(g["process_set_id"]), dtype, g["red_op"],
             float(g["prescale"]), float(g["postscale"]), int(n))
            for n in g["aux_sizes"])

    def _fp_payload(self, g: dict, prof) -> dict:
        lengths = [int(n) for n in g["aux_sizes"]]
        item = np.dtype(g["dtype"]).itemsize
        return {
            "sig": fastpath.schedule_sig(prof),
            "slots": [tuple(s) for s in prof],
            "lengths": lengths,
            "ends": fastpath.bucket_ends(
                [n * item for n in lengths],
                getattr(self.config, "overlap_buckets", 4),
                self.config.fusion_threshold_bytes),
            "process_set_id": int(g["process_set_id"]),
            "dtype": g["dtype"],
            "red_op": g["red_op"],
            "prescale": g["prescale"],
            "postscale": g["postscale"],
        }

    def _fp_cycle(self, g: dict):
        """Per-negotiated-record fast-path bookkeeping (exec thread,
        BEFORE the record executes).  A record arriving while frozen
        means some member kept negotiating — membership/world change;
        otherwise feed the warm streak and, when it trips, propose the
        freeze.  The flip happens before record K executes so a caller
        unblocked by K's handles stages K+1 against the frozen schedule
        on EVERY rank — rank 0's eligibility gate (every parked payload
        belongs to this record, i.e. no async caller is straddling the
        freeze point) is checked at the same record index on all
        members because records are coordinator-broadcast."""
        if self._fp.frozen() is not None:
            self._fp.thaw(
                "membership",
                detail="negotiated %s record arrived while frozen"
                % g["op_type"])
            return
        prof = self._fp_profile(g)
        if not self._fp.observe(prof):
            return
        with self._lock:
            quiesced = (self._failed is None
                        and len(self._pending) == len(g["entries"]))
        if self._fp.freeze(self._fp_payload(g, prof),
                           self._group_seq + 1, ok=quiesced):
            self._fp_core_set(True)

    def _fp_stage(self, name, op_type, arr, kw):
        """Caller-thread staging against the frozen schedule.  Returns
        a handle when the payload was staged (negotiation skipped), or
        None to fall through to full negotiation — including right
        after a loud shape thaw, whose flush has already renegotiated
        the staged prefix in program order."""
        if self._fp.frozen() is None:
            return None
        with self._fp_lock:
            fs = self._fp.frozen()
            if fs is None:
                return None
            i = self._fp_idx
            sig = self._fp_slot_sig(op_type, arr, kw)
            if i >= len(fs["slots"]) or tuple(fs["slots"][i]) != sig:
                self._fp.thaw(
                    "shape",
                    detail="staged %s %r does not match frozen slot %d"
                    % (op_type, name, i))
                return None
            py = CollectiveHandle(name)
            self._fp_pending.append((py, arr, name))
            self._fp_t = time.monotonic()
            self._fp_idx = i + 1
            self._m_bytes_submitted.inc(int(arr.nbytes))
            if self._fp_idx in fs["ends"]:
                if fastpath.stale_dispatch_seam():
                    # Injected stale frozen dispatch: thaw loudly and
                    # push the staged bucket back through full
                    # negotiation (the flush) — values stay correct,
                    # nothing hangs.
                    self._fp.thaw(
                        "staleness",
                        detail="injected stale dispatch "
                        "(engine.fastpath.stale_dispatch)")
                    return py
                start = self._fp_idx - len(self._fp_pending)
                bucket, self._fp_pending = self._fp_pending, []
                done = self._fp_idx >= len(fs["slots"])
                if done:
                    self._fp_idx = 0
                self._fp_q.put(self._fp_group(fs, bucket, start, done))
            return py

    def _fp_group(self, fs: dict, bucket, start: int, done: bool) -> dict:
        """Synthesize one frozen overlap bucket as a negotiated-group
        dict so dispatch reuses _execute verbatim (same watchdog,
        deadline, pipeline window and completion paths).  handle=-2
        marks entries with no core-side record to complete."""
        end = start + len(bucket)
        return {
            "op_type": "allreduce",
            "process_set_id": fs["process_set_id"],
            "dtype": fs["dtype"],
            "red_op": fs["red_op"],
            "prescale": fs["prescale"],
            "postscale": fs["postscale"],
            "aux_sizes": list(fs["lengths"][start:end]),
            "entries": [{"name": n, "handle": -2} for _, _, n in bucket],
            "_fp": True,
            "_fp_taken": [(py, arr) for py, arr, _ in bucket],
            "_fp_done": done,
            "_fp_t0": time.monotonic(),
        }

    def _fp_flush(self, fs: dict, reason: str):
        """Thaw flush (called under _fp_lock, inside the thaw — the
        re-entrant acquire below keeps the guard explicit): push the
        staged-but-undispatched bucket back through full negotiation in
        program order so every staged handle still resolves with
        correct values.  On a poisoned engine the handles error out
        instead — never silently dropped."""
        with self._fp_lock:
            bucket, self._fp_pending = self._fp_pending, []
            self._fp_idx = 0
        self._fp_core_set(False)
        if not bucket:
            return
        LOG.warning(
            "fast path: renegotiating %d staged tensor(s) after %s thaw",
            len(bucket), reason)
        for py, arr, name in bucket:
            with self._lock:
                if self._failed is not None:
                    if not py.poll():
                        py._set_error(HorovodInternalError(
                            "multihost engine disabled after watchdog "
                            "failure: %s" % self._failed))
                    continue
                ch = self.core.enqueue_external(
                    name, "allreduce", tuple(arr.shape),
                    np.dtype(arr.dtype), red_op=fs["red_op"],
                    process_set_id=fs["process_set_id"],
                    prescale=fs["prescale"], postscale=fs["postscale"])
                self._pending[ch._h] = (py, arr)
                self._m_queue_depth.set(len(self._pending))

    def _fp_core_set(self, on: bool):
        """Tell the native core to stretch its idle negotiation cadence
        while frozen (no requests will arrive)."""
        self.core.set_fastpath(bool(on))

    def _fp_idle_check(self):
        """Partial-cycle safety valve (exec thread, every drain tick):
        an app that stops enqueuing mid-bucket would otherwise park
        staged handles forever — after ~4 cycle times of staging
        silence, thaw loudly and renegotiate the staged prefix."""
        with self._fp_lock:
            if not self._fp_pending:
                return
            age = time.monotonic() - self._fp_t
            limit = max(0.05, 4.0 * self.config.cycle_time_ms / 1000.0)
            if age > limit:
                self._fp.thaw(
                    "shape",
                    detail="partial frozen cycle: %d staged tensor(s) "
                    "idle for %.2fs" % (len(self._fp_pending), age))

    # -- executor ----------------------------------------------------------

    def _loop(self):
        from ..core.client import parse_negotiated_record
        # Blocking wait in the core (condition variable): the executor
        # runs a record the instant negotiation finishes instead of
        # poll-sleeping half a cycle; the timeout only bounds shutdown
        # latency.
        wait_ms = max(int(self.config.cycle_time_ms), 1)
        while not self._shutdown:
            # Frozen overlap buckets dispatch ahead of negotiated
            # records: a staged bucket is already schedule-certain and
            # every record behind it (if any) postdates the freeze.
            try:
                while True:
                    g = self._fp_q.get_nowait()
                    try:
                        self._execute(g)
                    except Exception as exc:  # noqa: BLE001 - keep draining
                        LOG.error("multihost executor (frozen): %s", exc)
            except queue_mod.Empty:
                pass
            self._fp_idle_check()
            rec = self.core.wait_negotiated(wait_ms)
            if rec is None:
                # A stopped control plane (negotiation failure / peer
                # disconnect) will never negotiate the parked payloads:
                # fail them loudly instead of letting callers hang —
                # this is what lets elastic recovery proceed on worlds
                # where no execution watchdog is configured.
                if (self._failed is None and not self._shutdown
                        and self.core.stopped()):
                    self._poison(HorovodInternalError(
                        "control plane stopped (negotiation failed — "
                        "a member disconnected); failing pending "
                        "collectives"))
                continue
            if faultline.site("mh.drain.record"):
                # Injected negotiated-but-never-dispatched member: the
                # record is consumed and dropped, peers wedge inside
                # their compiled program — the execution watchdog's
                # scenario, on demand.
                LOG.error("faultline: dropping negotiated record")
                continue
            try:
                g = parse_negotiated_record(rec)
                try:
                    # Freeze coordination failing (KV timeout) must not
                    # strand the record: execute it regardless so its
                    # handles resolve; the world simply stays thawed.
                    self._fp_cycle(g)
                except Exception as exc:  # noqa: BLE001
                    LOG.error(
                        "fast-path freeze coordination failed: %s", exc)
                self._execute(g)
            except Exception as exc:  # noqa: BLE001 - keep draining
                LOG.error("multihost executor: %s", exc)

    def _take(self, handle: int):
        with self._lock:
            taken = self._pending.pop(handle, (None, None))
            self._m_queue_depth.set(len(self._pending))
            return taken

    # -- execution-phase watchdog ------------------------------------------

    def _watch_register(self, g, names, taken, entries,
                        deadline_secs: float = 0.0) -> int:
        with self._watch_lock:
            wid = self._watch_seq
            self._watch_seq += 1
            self._watched[wid] = {
                "g": g, "names": names, "taken": taken,
                "entries": entries, "start": time.monotonic(),
                "warned": False,
                # Per-collective deadline (0 = none): absolute bound on
                # this record's watched age.  The clock restarts at
                # compile end (_watch_compile), so a legitimate cold
                # compile is never charged against the deadline.
                "deadline_secs": max(float(deadline_secs), 0.0),
            }
        return wid

    def _watch_compile(self, wid: int, phase: str):
        """Cold-compile bracketing: while a compile runs, the record is
        marked so the watchdog holds fire (the executor thread is alive
        doing local work — charging compile time to the watched window
        would poison a healthy engine); at compile end the clock
        restarts so the window times execution only."""
        with self._watch_lock:
            rec = self._watched.get(wid)
            if rec is not None:
                rec["compiling"] = phase == "begin"
                if phase == "end":
                    rec["start"] = time.monotonic()
            # _last_progress is NOT advanced here: completions are the
            # only liveness signal.  Registering or compiling must not
            # push out detection of an already-wedged earlier group —
            # an app that keeps enqueuing (or keeps cold-compiling)
            # would otherwise starve the watchdog forever.

    def _watch_clear(self, wid: int) -> bool:
        """Remove the record; returns True if the watchdog already
        failed this group's handles (completion must not repeat it)."""
        with self._watch_lock:
            self._watched.pop(wid, None)
            killed = wid in self._killed_wids
            self._killed_wids.discard(wid)
            self._last_progress = time.monotonic()
        return killed

    def _watchdog_loop(self):
        strikes = 0
        while not self._shutdown:
            time.sleep(1.0)
            now = time.monotonic()
            with self._watch_lock:
                # Already-fired records stay in _watched until their
                # (wedged) program clears them, but must not re-fire
                # and re-log every tick.
                items = [(w, r) for w, r in self._watched.items()
                         if w not in self._killed_wids]
                idle = now - self._last_progress
            fired = False
            expired = []
            for wid, rec in items:
                if rec.get("compiling"):
                    # THIS record's own dispatch is mid-compile (local
                    # work, always terminates; its clock restarts at
                    # compile end) — don't charge compile time to its
                    # watched window.  Only the compiling record is
                    # skipped: a workload that keeps cold-compiling new
                    # shapes must not defer detection of an UNRELATED
                    # group that wedged after its own dispatch.
                    continue
                age = now - rec["start"]
                if (self._exec_warn and age > self._exec_warn
                        and not rec["warned"]):
                    rec["warned"] = True
                    LOG.warning(
                        "multihost %s group %s executing for %.0fs — a "
                        "member process may have died after negotiation "
                        "(device-plane stall)", rec["g"]["op_type"],
                        rec["names"], age)
                # Fire only when the whole pipeline is starved too: a
                # busy-but-healthy executor (deep queue, long compile)
                # keeps completing OTHER groups and must not be killed
                # for being slow.
                if (self._exec_timeout and age > self._exec_timeout
                        and idle > self._exec_timeout):
                    fired = True
                # Per-collective deadline: an ABSOLUTE bound on this
                # record alone — no idle gate, no strikes.  Unlike the
                # starvation watchdog, the deadline is a per-group
                # contract: other groups completing does not make THIS
                # group less wedged, and the operator sized the bound
                # for the size class (per-GiB scaling) on purpose.
                dl = rec.get("deadline_secs") or 0.0
                if dl > 0 and age > dl:
                    expired.append(rec)
            if expired:
                strikes = 0
                self._deadline_fire(expired)
                continue
            # Poisoning the engine is irreversible, so demand the
            # starved condition on consecutive ticks: a single tick can
            # straddle the instant a slow-but-healthy program completes
            # (progress lands right after the snapshot above).
            strikes = strikes + 1 if fired else 0
            if strikes >= 2:
                strikes = 0
                self._watchdog_fire()

    def _deadline_fire(self, expired):
        """Per-collective deadline expiry: count + journal each
        expired group, then error-complete everything outstanding and
        poison the engine through the fail-fast path.  The worker's
        pending handles raise :class:`CollectiveDeadlineExceeded` (a
        ``HorovodInternalError``), which the elastic recovery loop
        treats as restorable — its message must never contain the
        stall inspector's abort text, which would route elastic to the
        drain exit instead of restore-from-spill."""
        # Thaw BEFORE poisoning: the flush re-enqueues any staged
        # frozen bucket through the core while _failed is still unset,
        # so those handles land in the pending map and are swept into
        # the same loud deadline error as everything else — no hang,
        # and the next (recovered) engine starts from full negotiation.
        fastpath.thaw_all(
            "deadline", detail="per-collective deadline expired")
        for rec in expired:
            g = rec["g"]
            metrics.counter("collective_deadline_expired_total",
                            op=g["op_type"]).inc()
            metrics.event("collective_deadline_expired",
                          op=g["op_type"], names=list(rec["names"]),
                          deadline_secs=rec.get("deadline_secs"),
                          size_class=g.get("_metrics_class"))
        self._poison(lambda records: CollectiveDeadlineExceeded(
            "collective deadline exceeded: negotiated group(s) %s "
            "outlived their per-collective deadline "
            "(HOROVOD_COLLECTIVE_TIMEOUT_SECS, size-class scaled); "
            "error-completing outstanding handles and poisoning the "
            "engine so the elastic recovery loop restores from the "
            "last committed spill" % sorted(
                {rec["g"]["op_type"] + str(rec["names"])
                 for rec in records.values()})))

    def _watchdog_fire(self):
        """Fail every outstanding handle and poison the engine: the
        device program a dead member never joined will wedge the
        runtime thread forever, but callers get a loud diagnostic
        instead of hanging with it."""
        self._poison(lambda records: HorovodInternalError(
            "device execution watchdog: negotiated group(s) %s did not "
            "complete within %.1fs (HOROVOD_DEVICE_EXEC_TIMEOUT_SECONDS)"
            "; a member process likely died between negotiation and "
            "dispatch — failing outstanding handles" % (
                sorted({rec["g"]["op_type"] + str(rec["names"])
                        for rec in records.values()}),
                self._exec_timeout)))

    def _poison(self, exc_or_factory):
        """Fail every watched group and parked payload and reject new
        work — shared by the execution watchdog and the control-plane-
        stopped sweep.  A callable argument receives the ONE records
        snapshot that is actually failed, so the diagnostic can never
        name a group this sweep did not kill."""
        with self._watch_lock:
            records = {w: r for w, r in self._watched.items()
                       if w not in self._killed_wids}
            # Keep the records (cleared by _finish) but remember they
            # were killed, so a program that later unwedges does not
            # repeat completion on already-failed handles — and the
            # fire loop never re-fires them.
            self._killed_wids.update(records)
        exc = (exc_or_factory(records) if callable(exc_or_factory)
               else exc_or_factory)
        LOG.error("%s", exc)
        # _failed is set under the SAME lock that guards _enqueue's
        # check + park, so a racing enqueue either raises or lands in
        # the pending map swept here.
        with self._lock:
            self._failed = exc
            pending, self._pending = self._pending, {}
        for rec in records.values():
            self._complete_error(rec["g"], rec["names"], rec["taken"],
                                 rec["entries"], exc)
        # Payloads never dispatched (parked behind the wedged program)
        # fail too — and _enqueue rejects new work from here on.
        for py, _ in pending.values():
            if py is not None and not py.poll():
                py._set_error(exc)

    def _execute(self, g: dict):  # graftlint: schedule-entry=hier -- per-group dispatch order of the hierarchical DCN plane
        """Stage and dispatch one negotiated group, then hand the
        blocking tail (device_get for numpy-typed entries, handle
        resolution) to the completion thread — the drain loop is free
        to pop and dispatch group N+1 while N's program runs on
        device."""
        entries = g["entries"]
        if g.get("_fp"):
            # Frozen overlap bucket: payloads were staged caller-side,
            # nothing is parked in the core or the pending map
            # (handle=-2 entries skip core completion in _finish too).
            taken = g.pop("_fp_taken")
        else:
            taken = [self._take(e["handle"]) if e["handle"] >= 0
                     else (None, None) for e in entries]
        names = [e["name"] for e in entries]
        if g.get("error"):
            # Fail-fast record: the core refused to zero-fill a
            # negotiated entry missing on this non-joined rank.  Every
            # rank of the group must fail loudly, never complete with
            # a corrupted reduction: error-complete this group's
            # handles, then poison the engine (peers wedge inside the
            # program this rank never joins; their watchdog/stopped
            # sweep turns that into the same loud error).
            exc = HorovodInternalError(g["error"])
            self._complete_error(g, names, taken, entries, exc)
            self._poison(exc)
            return
        mc = self.collectives_for(g["process_set_id"])
        if self._failed is not None:
            self._complete_error(g, names, taken, entries, self._failed)
            return
        # Register BEFORE dispatch — on worlds where the compiled call
        # itself blocks until peers join (CPU gloo), a wedged dispatch
        # must already be watched.  Cold compiles run AOT inside
        # _compiled and report back via the per-dispatch ``notify``
        # callback, which restarts THIS group's clock: compile time
        # (local, legitimately long) is never charged to the watched
        # execution window.  The callback is threaded through the
        # dispatch call — never parked on the shared mesh object, where
        # a second executor would cross callbacks (graftlint
        # dispatch-scoped).
        group_bytes = sum(
            int(arr.nbytes) for _, arr in taken if arr is not None)
        deadline_secs = resilience.collective_deadline(group_bytes)
        wid = self._watch_register(g, names, taken, entries,
                                   deadline_secs)
        notify = lambda phase: self._watch_compile(wid, phase)  # noqa: E731
        # One negotiated group = one engine cycle in this mode; the
        # group id correlates the timeline span, the metrics gauge and
        # (below, via g) the completion-latency histogram.
        self._group_seq += 1
        gid = self._group_seq
        if g.get("_fp"):
            # A frozen schedule's buckets are one logical cycle: count
            # it ONCE (on the final bucket) and as a fast-path cycle,
            # never additionally as a negotiation cycle — levers.metrics
            # must attribute each cycle to exactly one path.
            if g.get("_fp_done"):
                self._m_fp_frozen.inc()
        else:
            self._m_cycles.inc()
        self._m_last_group.set(gid)
        if g["op_type"] == "allreduce" and len(entries) > 1:
            self._m_bytes_fused.inc(group_bytes)
            self._m_tensors_fused.inc(len(entries))
        g["_metrics_t0"] = time.monotonic()
        g["_metrics_class"] = _pow2_class(group_bytes)
        if faultline.site("mh.deadline.wedge"):
            # The group is registered and deadline-stamped but its
            # dispatch is withheld: the exact shape of a member whose
            # program never starts.  The watchdog's deadline check must
            # expire it -> error-complete -> poison -> elastic restore.
            LOG.error(
                "faultline: withholding dispatch of negotiated %s "
                "group %s (mh.deadline.wedge); the group stays watched "
                "until its per-collective deadline expires",
                g["op_type"], names)
            return
        # The leg guard bounds its retries by this group's absolute
        # deadline (thread-local: two executors may share one mesh).
        resilience.set_group_deadline(
            time.monotonic() + deadline_secs if deadline_secs > 0
            else None)
        try:
            # Per-tensor timeline span (reference: the EXEC_* phases the
            # native executors record) + an xprof TraceAnnotation so the
            # device program shows up named in jax profiler traces.
            import jax.profiler
            self.timeline.activity_start_all(
                names, "EXEC_DEVICE_" + g["op_type"].upper(),
                args={"group": gid})
            with jax.profiler.TraceAnnotation(
                    "hvd.mh.%s[%d]" % (g["op_type"], len(entries))):
                finalize, needs_host, rep = self._dispatch_group(
                    g, mc, taken, notify)
        except Exception as exc:  # noqa: BLE001
            if not self._watch_clear(wid):
                self._complete_error(g, names, taken, entries, exc)
            return
        finally:
            resilience.set_group_deadline(None)
        with self._lock:
            route_q = needs_host or self._host_inflight > 0
            if route_q:
                self._host_inflight += 1
        nbytes = 0
        if self.config.autotune and g["op_type"] == "allreduce":
            nbytes = int(sum(int(n) for n in g["aux_sizes"])
                         * np.dtype(g["dtype"]).itemsize)
        t0 = time.monotonic()
        if rep is not None:
            self._inflight_outs.append(rep)
            while len(self._inflight_outs) > self._depth:
                try:
                    self._inflight_outs.pop(0).block_until_ready()
                except Exception as exc:  # noqa: BLE001
                    # Handles were resolved at dispatch for
                    # device-resident groups; the failure would
                    # otherwise only surface when a consumer touches
                    # the array — leave a diagnostic trail here.
                    LOG.error(
                        "multihost device program failed after "
                        "dispatch-time completion: %s", exc)
        if route_q:
            # Blocking host fetch — or completions still in flight
            # whose relative order we keep — go through the completion
            # thread.  (_host_inflight is decremented only after
            # _finish fully resolves a queued group, so "zero" really
            # means every earlier group's handles are set.)
            self._done_q.put((g, names, taken, entries, finalize, wid,
                              nbytes, t0))
        else:
            # Device-resident group: finalize never blocks, so complete
            # inline and spare the cross-thread handoff (a scheduler
            # quantum per op on busy hosts).
            self._finish(g, names, taken, entries, finalize, wid)
            if nbytes and rep is not None:
                # Autotune signal: the completion thread blocks on the
                # output and reports true dispatch-to-completion time
                # (measuring at a later pipeline-window pop would add
                # arbitrary idle; the negotiation cycle says nothing
                # about async XLA payloads).
                self._done_q.put(("observe", rep, nbytes, t0))

    def _completion_loop(self):
        while True:
            item = self._done_q.get()
            if item is None:
                return
            if item[0] == "observe":
                # Device-resident group: block on its output, report
                # true completion time to the autotuner.
                _, rep, nbytes, t0 = item
                try:
                    rep.block_until_ready()
                except Exception as exc:  # noqa: BLE001
                    # The group's handles resolved ok=True at dispatch
                    # (device-resident inline completion); a runtime
                    # failure here must not be a throughput sample, and
                    # must not vanish — the consumer will hit it when
                    # touching the array, so leave the diagnostic now.
                    LOG.error(
                        "multihost device program failed after "
                        "dispatch-time completion (autotune observe): "
                        "%s", exc)
                    continue
                self._observe_exec(nbytes, t0)
                continue
            g, names, taken, entries, finalize, wid, nbytes, t0 = item
            ok = self._finish(g, names, taken, entries, finalize, wid)
            # Host-fetch completion IS the group's true completion —
            # but a failed/watchdog-killed group is not a throughput
            # sample.
            if ok:
                self._observe_exec(nbytes, t0)
            with self._lock:
                self._host_inflight -= 1

    def _observe_exec(self, nbytes, t0):
        if not nbytes:
            return
        try:
            self.core.autotune_observe(nbytes, time.monotonic() - t0)
        except Exception:  # noqa: BLE001 - optional feedback path
            pass

    def _finish(self, g, names, taken, entries, finalize, wid=None
                ) -> bool:
        """Resolve the group's handles; returns True only on a clean
        completion (False for errors or watchdog-killed groups, which
        must not become autotune throughput samples)."""
        try:
            results = finalize()
        except Exception as exc:  # noqa: BLE001 - keep draining
            if not (wid is not None and self._watch_clear(wid)):
                self._complete_error(g, names, taken, entries, exc)
            return False
        if wid is not None and self._watch_clear(wid):
            # The watchdog already failed this group's handles while
            # the program was wedged; a late completion must not
            # repeat external_done/release or overwrite the error.
            return False
        try:
            self.timeline.activity_end_all(names)
            for (py, _), res, e in zip(taken, results, entries):
                if e["handle"] >= 0:
                    self.core.external_done(e["handle"], ok=True)
                    self.core._lib.hvd_tcp_release(e["handle"])
                if py is not None and not py.poll():
                    py._set_result(res)
        except Exception as exc:  # noqa: BLE001 - keep draining
            self._complete_error(g, names, taken, entries, exc)
            return False
        # Dispatch-to-resolution latency per (op, pow2 size class);
        # only clean completions are samples — an error or watchdog
        # kill is not a latency observation.
        t0 = g.get("_metrics_t0")
        if t0 is not None:
            metrics.histogram(
                "mh_collective_seconds", op=g["op_type"],
                size_class=g.get("_metrics_class", "0")).observe(
                    time.monotonic() - t0)
            fp_t0 = g.get("_fp_t0")
            if fp_t0 is not None:
                # Per-bucket staging-to-completion latency of the
                # frozen fast path (the eager plane reports dispatch
                # time; here completion is the meaningful bound).
                self._m_fp_bucket.observe(time.monotonic() - fp_t0)
            now = time.monotonic()
            if now - self._cache_gauge_t >= 1.0:
                # Benign race on the throttle stamp (worst case one
                # extra refresh); the totals only move on a compile,
                # so per-completion recomputation would be waste.
                self._cache_gauge_t = now
                with self._lock:
                    caches = [mc._fns
                              for mc in self._collectives.values()]
                self._m_cache_hits.set(sum(c.hits for c in caches))
                self._m_cache_misses.set(sum(c.misses for c in caches))
        return True

    def _complete_error(self, g, names, taken, entries, exc):
        self.timeline.activity_end_all(names)
        LOG.error("multihost %s failed: %s", g["op_type"], exc)
        # The failure-side complement of mh_collective_seconds (which
        # only records clean completions): every error-completed group
        # is visible in the fleet merge, bucketed by why it died.
        metrics.counter("mh_collective_failures_total", op=g["op_type"],
                        reason=resilience.failure_reason(exc)).inc()
        for (py, _), e in zip(taken, entries):
            if e["handle"] >= 0:
                self.core.external_done(e["handle"], ok=False,
                                        error=str(exc))
                self.core._lib.hvd_tcp_release(e["handle"])
            if py is not None and not py.poll():
                py._set_error(exc)

    @staticmethod
    def _match(out, arr, shape=None):
        """Shape a program output like the caller's input: device
        arrays stay device-resident (eager reshape only), numpy inputs
        get numpy back.  This is the single conversion point — the
        GlobalMeshCollectives methods always return device arrays."""
        import jax
        import jax.numpy as jnp
        if arr is not None and _is_device_array(arr):
            return jnp.reshape(out, shape) if shape is not None else out
        host = np.asarray(jax.device_get(out))
        return host.reshape(shape) if shape is not None else host

    def _dispatch_group(self, g: dict, mc: GlobalMeshCollectives,
                        taken: List[tuple],
                        notify=None):  # graftlint: hot-path
        """Issue the group's compiled collective (async XLA dispatch)
        and return ``(finalize, needs_host, rep)``: a finalize() ->
        results closure, whether it blocks on a host fetch (numpy-typed
        entries), and one representative output array of the dispatched
        program (for the drain thread's pipeline-depth window).
        Blocking finalizes run only on the completion thread;
        device-resident ones may complete inline.  ``notify`` is this
        dispatch's cold-compile bracket, threaded down to
        ``mc._compiled``."""
        op = g["op_type"]
        dtype = g["dtype"]
        if op == "allreduce":
            # Fused group in negotiated order (missing = joined rank ->
            # zero contribution, synthesized on device).  One compiled
            # program takes every entry and XLA's all-reduce combiner
            # fuses the collectives; payloads never transit numpy.
            # The controller rejects joined + Min/Max/Product/Adasum at
            # negotiation and rewrites Average to Sum with a live-count
            # divisor; by the time a zero-fill reaches this executor the
            # reduction must be Sum (the only op whose identity is zero).
            if (any(arr is None for _, arr in taken)
                    and g["red_op"] != SUM):
                raise HorovodInternalError(
                    "zero-contribution join reached the executor with "
                    "op=%s; only Sum may be zero-filled" % g["red_op"])
            lengths = [int(n) for n in g["aux_sizes"]]
            outs = mc.fused_allreduce(
                [arr for _, arr in taken], lengths, dtype,
                g["red_op"], g["prescale"], g["postscale"], notify,
                names=[e["name"] for e in g["entries"]])
            needs_host = any(arr is None or not _is_device_array(arr)
                             for _, arr in taken)

            def finalize():
                # One batched device_get for every numpy-typed entry (a
                # per-entry fetch would serialize N host round-trips on
                # the thread that gates all handles).
                import jax
                import jax.numpy as jnp
                to_host = [i for i, (_, arr) in enumerate(taken)
                           if arr is None or not _is_device_array(arr)]
                fetched = dict(zip(to_host, jax.device_get(  # graftlint: disable=host-bounce issue=ISSUE-1 -- THE documented batched fetch for numpy-typed entries; runs on the completion thread only
                    [outs[i] for i in to_host]))) if to_host else {}
                results = []
                for i, ((py, arr), out, ln) in enumerate(
                        zip(taken, outs, lengths)):
                    shape = arr.shape if arr is not None else (ln,)
                    if i in fetched:
                        results.append(
                            np.asarray(fetched[i]).reshape(shape))  # graftlint: disable=host-bounce issue=ISSUE-1 -- reshape of already-fetched host data, no device sync
                    else:
                        results.append(jnp.reshape(out, shape))
                return results
            return finalize, needs_host, outs[0]
        (py, arr) = taken[0]
        needs_host = arr is None or not _is_device_array(arr)
        if op == "allgather":
            out = mc.allgather(arr, g["aux_sizes"], notify)
            return (lambda: [self._match(out, arr)]), needs_host, out
        if op == "broadcast":
            # root_rank is a GLOBAL rank; map to member index.
            ranks = self._resolve_process_set(g["process_set_id"])
            members = ranks if ranks is not None else list(
                range(mc.size))
            root_idx = members.index(g["root_rank"])
            out = mc.broadcast(arr, root_idx, notify)
            return (lambda: [self._match(out, arr)]), needs_host, out
        if op == "alltoall":
            out, recv = mc.alltoall(arr, np.asarray(g["aux_sizes"]),  # graftlint: disable=host-bounce issue=ISSUE-1 -- negotiated splits metadata, never payload bytes
                                    notify)
            return ((lambda: [(self._match(out, arr), recv)]),
                    needs_host, out)
        if op == "reducescatter":
            out = mc.reducescatter(arr, g["red_op"], notify,
                                   name=g["entries"][0]["name"])
            return (lambda: [self._match(out, arr)]), needs_host, out
        raise NotImplementedError("multihost op %r" % op)

    # -- shutdown ----------------------------------------------------------

    def shutdown(self):
        # Thaw first (flush re-parks staged payloads in the pending
        # map, swept into "engine shut down" errors below), and drop
        # out of the thaw_all registry before the drain thread dies.
        self._fp.thaw("membership", detail="engine shutdown")
        fastpath.unregister(self._fp)
        self._shutdown = True
        self._thread.join(timeout=10.0)
        # Stop the completion thread with a sentinel AFTER the queued
        # work, so every dispatched group still resolves its handles.
        # The put is bounded: the queue may be permanently full if a
        # completion is wedged on a collective whose peer died — give
        # up after the deadline (the thread is a daemon) rather than
        # hanging shutdown in exactly the failure it must clean up.
        deadline = time.monotonic() + 10.0
        while True:
            try:
                self._done_q.put_nowait(None)
                break
            except queue_mod.Full:
                if (time.monotonic() > deadline
                        or not self._done_thread.is_alive()):
                    break
                time.sleep(0.05)
        self._done_thread.join(timeout=10.0)
        # Fail anything stranded: groups still queued (a wedged
        # completion, or a drain thread that outlived its join and
        # enqueued past the sentinel) would otherwise leave their
        # already-_take()n handles unresolved forever.
        while True:
            try:
                item = self._done_q.get_nowait()
            except queue_mod.Empty:
                break
            if item is None or item[0] == "observe":
                continue
            g, names, taken, entries = item[:4]
            self._complete_error(
                g, names, taken, entries,
                HorovodInternalError("engine shut down"))
        with self._lock:
            pending, self._pending = self._pending, {}
        for py, _ in pending.values():
            if not py.poll():
                py._set_error(
                    HorovodInternalError("engine shut down"))
