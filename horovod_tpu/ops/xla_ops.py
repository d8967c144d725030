"""XLA collective executables over a device mesh.

TPU-native replacement for the reference's vendor-collective backends
(``horovod/common/ops/nccl_operations.cc`` / ``mpi_operations.cc`` /
``gloo_operations.cc``): on TPU there is no NCCL-style library call —
collectives are XLA HLO ops (``all-reduce``, ``all-gather``,
``all-to-all``, ``reduce-scatter``, ``collective-permute``) compiled via
PJRT and executed over ICI (within a slice) / DCN (across slices).  This
module builds and caches those tiny compiled executables; the engine
(``horovod_tpu.ops.engine``) feeds them fused buffers.

Eager tensor convention (single-controller SPMD world): a collective input
is "rank-major stacked" — leading axis indexes ranks, i.e. ``x[r]`` is what
rank ``r`` contributes.  The engine shards that axis over the mesh so every
device holds exactly its own contribution, then runs the collective.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .executable_cache import ExecutableCache

AXIS = "hvd"

# Reduction ops (reference: horovod/common/common.h ReduceOp enum).
SUM = "Sum"
AVERAGE = "Average"
MIN = "Min"
MAX = "Max"
PRODUCT = "Product"
ADASUM = "Adasum"

_REDUCE_OPS = (SUM, AVERAGE, MIN, MAX, PRODUCT, ADASUM)


def alltoall_chunk_reduce(x, axis_name: str, size: int, red_op: str):
    """Bytes-proportional Min/Max/Product reduce-scatter (per-shard
    code): ``x`` [size*k, ...] -> this shard's reduced [k, ...] chunk
    via one ``all_to_all`` + a local reduce.  1× payload bytes on the
    wire — the all-gather fallback these ops used moved N× — with
    exact arithmetic (no log/exp decomposition).  An allreduce is this
    plus a tiled all_gather (2× total, the Sum paths' bus bytes)."""
    from jax import lax
    import jax.numpy as jnp
    k = x.shape[0] // size
    blocks = x.reshape((size, k) + x.shape[1:])
    w = lax.all_to_all(blocks, axis_name, split_axis=0, concat_axis=0)
    if red_op == MIN:
        return w.min(axis=0)
    if red_op == MAX:
        return w.max(axis=0)
    if red_op == PRODUCT:
        return jnp.prod(w, axis=0)
    raise NotImplementedError("chunk reduce op %r" % red_op)


def product_allreduce(flat, axis_name: str, size: int):
    """Exact bytes-proportional Product allreduce (per-shard code):
    reduce-scatter chunks via ``alltoall_chunk_reduce``, then a tiled
    all_gather — ~2× payload bytes like the Sum path, instead of the
    N× of all_gather + local product."""
    from jax import lax
    import jax.numpy as jnp
    n = flat.shape[0]
    if n == 0 or size == 1:
        return flat
    c = -(-n // size)
    if size * c > n:
        flat = jnp.concatenate(
            [flat, jnp.ones((size * c - n,), flat.dtype)])
    chunk = alltoall_chunk_reduce(flat, axis_name, size, PRODUCT)
    full = lax.all_gather(chunk, axis_name, tiled=True)
    return full[:n]


def host_or_device(x):
    """``x`` as the engine carries it until placement: a ``jax.Array``
    stays on its devices, anything else becomes host numpy in jax's
    canonical dtype.  Host data is never committed to the default
    device on the way: ``MeshCollectives.shard_stacked`` sends each
    row straight to its own chip."""
    if isinstance(x, jax.Array):
        return x
    x = np.asarray(x)
    return x.astype(jax.dtypes.canonicalize_dtype(x.dtype), copy=False)


def stack_rows(rows):
    """Rank-major stack of per-rank pieces; built on the host unless a
    piece already lives on a device."""
    if any(isinstance(r, jax.Array) for r in rows):
        return jnp.stack([jnp.asarray(r) for r in rows])
    return host_or_device(np.stack([np.asarray(r) for r in rows]))


def uneven_chunks(total_rows: int, n: int):
    """Reference ReducescatterOp chunk math: earlier members take the
    larger shards (cpu_ops.cc uses the same base/remainder split).
    Shared by the in-process engine and multihost mode so the shard
    boundaries can never desynchronize."""
    base, rem = divmod(total_rows, n)
    rows = [base + (1 if i < rem else 0) for i in range(n)]
    offs = [sum(rows[:i]) for i in range(n)]
    return rows, offs


def handle_average_backwards_compatibility(op, average):
    """Reconcile the legacy ``average=`` kwarg with ``op=`` (reference:
    horovod/common/util.py check_num_rank_power_of_2 /
    handle_average_backwards_compatibility)."""
    if op is not None and average is not None:
        raise ValueError("`average` and `op` are mutually exclusive")
    if op is None:
        if average is None or average:
            return AVERAGE
        return SUM
    return op


class MeshCollectives:
    """Compiled XLA collectives over one mesh (one per process set).

    Each public method returns the result of a cached compiled executable;
    compile cache keys are (op, dtype, shape/bucket), so steady-state
    training dispatches without retracing — the XLA analog of the
    reference's response-cache fast path.
    """

    def __init__(self, devices: Sequence, cache: Optional[ExecutableCache] = None,
                 name: str = "global"):
        self.devices = list(devices)
        self.size = len(self.devices)
        self.name = name
        self.mesh = Mesh(np.asarray(self.devices), (AXIS,))
        self.cache = cache if cache is not None else ExecutableCache()
        self._stacked_sharding = NamedSharding(self.mesh, P(AXIS))
        self._replicated_sharding = NamedSharding(self.mesh, P())
        # Sightings per (shape, splits) / grouping key: compiled fused
        # programs are built only for keys that repeat.
        self._ragged_seen: dict = {}
        self._grouping_seen: dict = {}

    # -- helpers -----------------------------------------------------------

    def shard_stacked(self, x):
        """Place a rank-major stacked array so row r lives on device r."""
        return jax.device_put(host_or_device(x), self._stacked_sharding)

    def _key(self, op: str, dtype, shape, extra=()) -> tuple:
        return (self.name, op, str(dtype), tuple(shape)) + tuple(extra)

    # -- allreduce ---------------------------------------------------------

    def _allreduce_shard_fn(self, red_op: str):
        """The unjitted shard_map collective, shared by the plain and
        fused allreduce programs."""
        size = self.size

        def block_fn(x, pre, post):
            # x: this rank's block [1, ...]; pre/post: scalar factors.
            x = x * pre.astype(x.dtype)
            if red_op in (SUM, AVERAGE, ADASUM):
                r = lax.psum(x, AXIS)
                if red_op == AVERAGE:
                    # Average in f32 accumulation for low-precision inputs.
                    r = (r / size).astype(x.dtype) if jnp.issubdtype(
                        x.dtype, jnp.floating) else r // size
            elif red_op == MIN:
                r = lax.pmin(x, AXIS)
            elif red_op == MAX:
                r = lax.pmax(x, AXIS)
            elif red_op == PRODUCT:
                r = product_allreduce(
                    x.reshape(-1), AXIS, size).reshape(x.shape)
            else:
                raise NotImplementedError(red_op)
            return r * post.astype(x.dtype)

        # check_vma off for Product: the reduce-scatter + tiled
        # all_gather result is replicated in value but not statically
        # inferable as such.
        return jax.shard_map(block_fn, mesh=self.mesh,
                             in_specs=(P(AXIS), P(), P()),
                             out_specs=P(), check_vma=(red_op != PRODUCT))

    def _build_allreduce(self, red_op: str):
        return jax.jit(self._allreduce_shard_fn(red_op))

    def allreduce(self, stacked, red_op: str = SUM,
                  prescale_factor: float = 1.0,
                  postscale_factor: float = 1.0):
        """Reduce rank-major stacked [size, ...] -> replicated [...]."""
        stacked = self.shard_stacked(stacked)
        key = self._key("allreduce", stacked.dtype, stacked.shape, (red_op,))
        fn = self.cache.get_or_build(
            key, lambda: self._build_allreduce(red_op))
        pre = jnp.asarray(prescale_factor, dtype=jnp.float32)
        post = jnp.asarray(postscale_factor, dtype=jnp.float32)
        out = fn(stacked, pre, post)
        # Block shape [1, ...] -> logical [...]
        return out[0]

    def _build_fused_allreduce(self, red_op, shapes, joined_idx, bucket):
        size = self.size
        shard_fn = self._allreduce_shard_fn(red_op)
        lengths = [int(np.prod(s[1:], dtype=np.int64)) for s in shapes]
        total = sum(lengths)

        def prog(pre, post, *payloads):
            flats = []
            for p, joined in zip(payloads, joined_idx):
                f = p.reshape(size, -1)
                if joined:
                    f = f.at[jnp.asarray(list(joined))].set(0)
                flats.append(f)
            if bucket > total:
                flats.append(jnp.zeros((size, bucket - total),
                                       dtype=flats[0].dtype))
            fused = jnp.concatenate(flats, axis=1)
            out = shard_fn(fused, pre, post)[0]
            outs, off = [], 0
            for ln, s in zip(lengths, shapes):
                outs.append(out[off:off + ln].reshape(s[1:]))
                off += ln
            return tuple(outs)

        return jax.jit(prog)

    def fused_allreduce(self, payloads, red_op: str,
                        prescale_factor: float, postscale_factor: float,
                        joined_idx, bucket: int):
        """Fusion-group allreduce.

        A grouping seen for the SECOND time gets one compiled program
        (flatten + zero joined rows + concat into the padded bucket +
        the collective + per-entry slices — XLA owns the fusion
        buffer as compiler scratch).  A first-seen grouping takes the
        eager path, whose big collective executable is keyed only on
        the power-of-two bucket shape and therefore shared across
        groupings — so shifting chunk boundaries (e.g. while the
        autotuner moves the fusion threshold) don't compile a fresh
        program every cycle."""
        payloads = [self.shard_stacked(p) for p in payloads]
        joined_idx = tuple(tuple(j) for j in joined_idx)
        shapes = tuple(p.shape for p in payloads)
        key = self._key("fused_allreduce", payloads[0].dtype, shapes,
                        (red_op, joined_idx, bucket))
        if len(self._grouping_seen) > 4096:  # bound the sighting memo
            self._grouping_seen.clear()
        seen = self._grouping_seen.get(key, 0)
        self._grouping_seen[key] = seen + 1
        pre = jnp.asarray(prescale_factor, dtype=jnp.float32)
        post = jnp.asarray(postscale_factor, dtype=jnp.float32)
        if seen == 0:
            flats = []
            for p, joined in zip(payloads, joined_idx):
                f = p.reshape(self.size, -1)
                if joined:
                    f = f.at[jnp.asarray(list(joined))].set(0)
                flats.append(f)
            lengths = [f.shape[1] for f in flats]
            total = sum(lengths)
            if bucket > total:
                flats.append(jnp.zeros((self.size, bucket - total),
                                       dtype=flats[0].dtype))
            fused = jnp.concatenate(flats, axis=1)
            out = self.allreduce(fused, red_op, prescale_factor,
                                 postscale_factor)
            outs, off = [], 0
            for ln, s in zip(lengths, shapes):
                outs.append(out[off:off + ln].reshape(s[1:]))
                off += ln
            return tuple(outs)
        fn = self.cache.get_or_build(
            key, lambda: self._build_fused_allreduce(
                red_op, shapes, joined_idx, bucket))
        return fn(pre, post, *payloads)

    # -- allgather ---------------------------------------------------------

    def _build_allgather(self):
        def block_fn(x):
            # x: [1, k, ...] -> gather to [size*k, ...] on every rank.
            g = lax.all_gather(x[0], AXIS, tiled=True)
            return g

        fn = jax.shard_map(block_fn, mesh=self.mesh,
                           in_specs=P(AXIS), out_specs=P(),
                           check_vma=False)
        return jax.jit(fn)

    def allgather(self, per_rank: List):
        """Concatenate per-rank tensors along axis 0 (ragged allowed).

        Matches reference AllgatherOp semantics: first dims may differ
        across ranks; other dims must match.
        """
        dims0 = {np.shape(t)[0] if np.ndim(t) else 1 for t in per_rank}
        if len(dims0) == 1:
            stacked = self.shard_stacked(stack_rows(per_rank))
            key = self._key("allgather", stacked.dtype, stacked.shape)
            fn = self.cache.get_or_build(key, self._build_allgather)
            return fn(stacked)
        # Ragged path: single-controller concat, compiled per shape-sig.
        sig = tuple(tuple(np.shape(t)) for t in per_rank)
        key = self._key("allgather_ragged", np.asarray(per_rank[0]).dtype, (), (sig,))
        fn = self.cache.get_or_build(
            key, lambda: jax.jit(
                lambda *ts: jnp.concatenate(ts, axis=0),
                out_shardings=self._replicated_sharding))
        return fn(*[jnp.asarray(t) for t in per_rank])

    # -- broadcast ---------------------------------------------------------

    def broadcast(self, stacked, root_rank: int):
        """Select rank ``root``'s row and replicate it to all devices."""
        stacked = self.shard_stacked(stacked)
        key = self._key("broadcast", stacked.dtype, stacked.shape)
        fn = self.cache.get_or_build(
            key,
            lambda: jax.jit(
                lambda x, r: lax.dynamic_index_in_dim(
                    x, r, axis=0, keepdims=False),
                out_shardings=self._replicated_sharding))
        return fn(stacked, jnp.asarray(root_rank, dtype=jnp.int32))

    # -- alltoall ----------------------------------------------------------

    def _build_alltoall(self):
        def block_fn(x):
            # x: [1, size*k, ...]; split dim1 into `size` chunks, chunk j
            # goes to rank j; received chunks concatenate along dim1.
            y = lax.all_to_all(x[0], AXIS, split_axis=0, concat_axis=0,
                               tiled=True)
            return y[None]

        fn = jax.shard_map(block_fn, mesh=self.mesh,
                           in_specs=P(AXIS), out_specs=P(AXIS))
        return jax.jit(fn)

    def alltoall(self, stacked, splits: Optional[np.ndarray] = None):
        """All-to-all exchange.

        ``stacked``: [size, N, ...] where rank r's tensor is ``stacked[r]``.
        Uniform case (``splits is None`` and N % size == 0): compiled XLA
        ``all-to-all``.  Ragged case (per-rank split sizes, reference
        ``AlltoallOp`` with ``splits`` argument): single-controller
        reassembly; returns (stacked_out_list, recv_splits).
        """
        stacked = host_or_device(stacked)
        n = stacked.shape[1] if stacked.ndim > 1 else 0
        if splits is None:
            if stacked.shape[0] != self.size or n % self.size != 0:
                raise ValueError(
                    "uniform alltoall needs dim1 divisible by size")
            stacked = self.shard_stacked(stacked)
            key = self._key("alltoall", stacked.dtype, stacked.shape)
            fn = self.cache.get_or_build(key, self._build_alltoall)
            return fn(stacked), None
        # Ragged: splits[r][j] = #rows rank r sends to rank j.
        #
        # Output shapes depend on the exact splits matrix, so a
        # compiled program is only worth building for splits that
        # REPEAT (e.g. fixed-capacity MoE dispatch); per-step varying
        # splits would recompile every step.  First sighting (or a
        # pathologically skewed pad) takes the eager reassembly; a
        # repeat compiles one program that fuses the pack/unpack
        # around a single device all_to_all collective.
        splits = np.asarray(splits)
        maxc = int(splits.max(initial=0))
        if maxc == 0:
            empty = stacked[:, :0] if stacked.ndim > 1 else stacked[:0]
            return [empty[0] for _ in range(self.size)], splits.T.copy()
        key = self._key("alltoall_ragged", stacked.dtype, stacked.shape,
                        (splits.tobytes(),))
        pad_blowup = (self.size * self.size * maxc
                      > 4 * int(splits.sum()))
        if len(self._ragged_seen) > 4096:  # bound the sighting memo
            self._ragged_seen.clear()
        seen = self._ragged_seen.get(key, 0)
        self._ragged_seen[key] = seen + 1
        if pad_blowup or seen == 0:
            out_rows: List[List] = [[] for _ in range(self.size)]
            for r in range(self.size):
                off = 0
                for j in range(self.size):
                    c = int(splits[r, j])
                    out_rows[j].append(stacked[r][off:off + c])
                    off += c
            outs = [jnp.concatenate(rows, axis=0) for rows in out_rows]
            return outs, splits.T.copy()
        fn = self.cache.get_or_build(
            key, lambda: self._build_alltoall_ragged(splits))
        return list(fn(stacked)), splits.T.copy()

    def _build_alltoall_ragged(self, splits: np.ndarray):
        size = self.size
        maxc = int(splits.max())

        def block_fn(x):
            # x: [1, size, maxc, ...] -> row j to rank j; received rows
            # stack in sender order.
            y = lax.all_to_all(x[0], AXIS, split_axis=0, concat_axis=0,
                               tiled=True)
            return y[None]

        shuffle = jax.shard_map(block_fn, mesh=self.mesh,
                                in_specs=P(AXIS), out_specs=P(AXIS))

        def prog(stacked):
            rest_ndim = stacked.ndim - 2
            send = []
            for r in range(size):
                off, chunks = 0, []
                for j in range(size):
                    c = int(splits[r, j])
                    blk = stacked[r, off:off + c]
                    off += c
                    chunks.append(jnp.pad(
                        blk, [(0, maxc - c)] + [(0, 0)] * rest_ndim))
                send.append(jnp.stack(chunks))
            recv = shuffle(jnp.stack(send))  # [recv_rank, send_rank, maxc, ...]
            outs = []
            for j in range(size):
                rows = [recv[j, r, :int(splits[r, j])]
                        for r in range(size)]
                outs.append(jnp.concatenate(rows, axis=0))
            return tuple(outs)

        return jax.jit(prog)

    # -- reducescatter -----------------------------------------------------

    # (uneven chunk layout shared with the engine and multihost mode
    # lives in module scope: uneven_chunks below)

    def _build_reducescatter(self, red_op: str):
        size = self.size

        def block_fn(x):
            # x: [1, size*k, ...] -> this rank's reduced shard [k, ...].
            if red_op in (SUM, AVERAGE):
                y = lax.psum_scatter(x[0], AXIS, scatter_dimension=0,
                                     tiled=True)
                if red_op == AVERAGE:
                    y = (y / size).astype(y.dtype)
            else:
                # No scatter-variant collective exists for these ops;
                # one all_to_all + a local reduce keeps the wire at 1×
                # payload bytes (the full-reduce-then-slice fallback
                # moved N×).
                y = alltoall_chunk_reduce(x[0], AXIS, size, red_op)
            return y[None]

        fn = jax.shard_map(block_fn, mesh=self.mesh,
                           in_specs=P(AXIS), out_specs=P(AXIS))
        return jax.jit(fn)

    def reducescatter(self, stacked, red_op: str = SUM):
        """[size, N, ...] -> [size, N/size, ...]: row r is rank r's reduced
        shard.  Requires N % size == 0; the engine routes uneven N
        through a full reduce + chunk slicing that matches the native
        core's layout (reference ReducescatterOp gives earlier ranks
        the larger shards)."""
        stacked = self.shard_stacked(stacked)
        key = self._key("reducescatter", stacked.dtype, stacked.shape,
                        (red_op,))
        fn = self.cache.get_or_build(
            key, lambda: self._build_reducescatter(red_op))
        return fn(stacked)

    # -- barrier -----------------------------------------------------------

    def barrier(self):
        """Device-visible barrier: a tiny psum all must participate in."""
        one = jnp.ones((self.size,), dtype=jnp.int32)
        out = self.allreduce(one.reshape(self.size, 1), SUM)
        jax.block_until_ready(out)
        return int(out[0])
