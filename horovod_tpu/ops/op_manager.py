"""Pluggable collective backend registry with a priority walk.

Reference parity: ``horovod/common/ops/operation_manager.cc`` — per-op
priority lists where the first backend whose ``Enabled(entries)`` test
passes executes the op (there: NCCL > DDL > GPU > MPI > Gloo ...).
TPU translation: the planes are ICI/DCN device collectives (in-process
engine or multihost engine) and host-TCP CPU collectives (the native
core).  Selection is per-request — a backend may accept large device
payloads and decline tiny host-side ones, or vice versa — and the walk
order can be overridden with ``HVD_TPU_BACKENDS`` / ``HOROVOD_BACKENDS``
(comma list of backend names, highest priority first) or extended at
runtime with :func:`register_backend`.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import numpy as np

from .engine import HorovodInternalError

DEVICE_OPS = ("allreduce", "allgather", "broadcast", "alltoall",
              "reducescatter")


class OpRequest:
    """One collective submission (a group for grouped allreduce)."""

    __slots__ = ("op_type", "tensors", "names", "red_op", "prescale",
                 "postscale", "root_rank", "splits", "process_set_id",
                 "ps_size", "is_group")

    def __init__(self, op_type, tensors, names, red_op=None, prescale=1.0,
                 postscale=1.0, root_rank=0, splits=None,
                 process_set_id=0, ps_size=1, is_group=False):
        self.op_type = op_type
        self.tensors = tensors        # list (len 1 unless is_group)
        self.names = names            # matching names
        self.red_op = red_op
        self.prescale = prescale
        self.postscale = postscale
        self.root_rank = root_rank
        self.splits = splits
        self.process_set_id = process_set_id
        self.ps_size = ps_size
        self.is_group = is_group

    def __repr__(self):
        return "OpRequest(%s, %s)" % (self.op_type, self.names)


class CollectiveBackend:
    """Base class (reference ``HorovodOp`` + the manager's entries).

    ``enabled`` may inspect the request per-tensor; the first enabled
    backend in priority order wins.  ``submit`` returns one handle, or a
    list of handles for a group request.
    """

    name = "backend"

    def enabled(self, req: OpRequest) -> bool:
        raise NotImplementedError

    def submit(self, req: OpRequest):
        raise NotImplementedError


class OpManager:
    """Priority walk over registered backends (operation_manager.cc)."""

    def __init__(self, backends: Sequence[CollectiveBackend]):
        self.backends: List[CollectiveBackend] = list(backends)

    def register(self, backend: CollectiveBackend, index: int = 0):
        """Insert a backend at priority ``index`` (0 = highest)."""
        self.backends.insert(index, backend)

    def submit(self, req: OpRequest):
        for b in self.backends:
            if b.enabled(req):
                return b.submit(req)
        raise HorovodInternalError(
            "no enabled backend for %r (registered: %s)"
            % (req, [b.name for b in self.backends]))

    def backend_for(self, req: OpRequest) -> Optional[str]:
        """Name of the backend the walk would select (introspection)."""
        for b in self.backends:
            if b.enabled(req):
                return b.name
        return None


def order_from_env(backends: Sequence[CollectiveBackend], env: str
                   ) -> List[CollectiveBackend]:
    """Reorder/filter builtin backends per the env override; unknown
    names raise (a typo silently dropping a plane would be miserable to
    debug at pod scale)."""
    names = [n.strip() for n in env.split(",") if n.strip()]
    by_name = {b.name: b for b in backends}
    unknown = [n for n in names if n not in by_name]
    if unknown:
        raise ValueError(
            "unknown backend(s) %s in backend order override; available: %s"
            % (unknown, sorted(by_name)))
    return [by_name[n] for n in names]


# -- builtin backends -------------------------------------------------------


def _np(tensor):
    return np.ascontiguousarray(np.asarray(tensor))


class MultihostIciBackend(CollectiveBackend):
    """Device payload plane of multihost mode: the native core
    negotiates order, the multihost engine executes compiled XLA
    collectives over the global mesh (ICI/DCN on pods)."""

    name = "multihost_ici"

    def __init__(self, get_engine: Callable, get_core: Callable):
        self._get_engine = get_engine
        self._get_core = get_core

    def enabled(self, req: OpRequest) -> bool:
        from .xla_ops import ADASUM
        # Adasum allreduce is device-resident (adasum_combine: ppermute
        # XOR-tree under shard_map — the adasum_gpu_operations.cc
        # analog); other Adasum ops stay on the host plane (TreeAdasum).
        if req.red_op == ADASUM and req.op_type != "allreduce":
            return False
        return req.op_type in DEVICE_OPS

    def submit(self, req: OpRequest):
        eng = self._get_engine()
        if req.is_group:
            # Atomic negotiation for any grouped op (reference
            # group_table.cc covers allgather/reducescatter too).
            self._get_core().register_group(req.names)
        if req.op_type == "allreduce":
            hs = [eng.enqueue_allreduce(
                n, t, red_op=req.red_op, prescale=req.prescale,
                postscale=req.postscale, process_set_id=req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        if req.op_type == "allgather":
            hs = [eng.enqueue_allgather(
                n, t, process_set_id=req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        if req.op_type == "reducescatter":
            hs = [eng.enqueue_reducescatter(
                n, t, red_op=req.red_op,
                process_set_id=req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        t, n = req.tensors[0], req.names[0]
        if req.op_type == "broadcast":
            return eng.enqueue_broadcast(
                n, t, root_rank=req.root_rank,
                process_set_id=req.process_set_id)
        if req.op_type == "alltoall":
            splits = (None if req.splits is None
                      else list(np.asarray(req.splits)))
            return eng.enqueue_alltoall(
                n, t, splits=splits,
                process_set_id=req.process_set_id)
        raise HorovodInternalError("unsupported op %s" % req.op_type)


class HostTcpBackend(CollectiveBackend):
    """Host payload plane: the native core moves bytes over TCP rings
    (the reference's Gloo CPU path; also Adasum's home)."""

    name = "host_tcp"

    def __init__(self, get_core: Callable):
        self._get_core = get_core

    def enabled(self, req: OpRequest) -> bool:
        return req.op_type in DEVICE_OPS

    def submit(self, req: OpRequest):
        core = self._get_core()
        if req.is_group:
            core.register_group(req.names)
        if req.op_type == "allreduce":
            hs = [core.allreduce_async(
                _np(t), n, op=req.red_op, prescale=req.prescale,
                postscale=req.postscale, process_set_id=req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        if req.op_type == "allgather":
            hs = [core.allgather_async(
                _np(t), n, process_set_id=req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        if req.op_type == "reducescatter":
            hs = [core.reducescatter_async(
                _np(t), n, op=req.red_op,
                process_set_id=req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        t, n = req.tensors[0], req.names[0]
        if req.op_type == "broadcast":
            return core.broadcast_async(
                _np(t), n, root_rank=req.root_rank,
                process_set_id=req.process_set_id)
        if req.op_type == "alltoall":
            splits = (None if req.splits is None
                      else list(np.asarray(req.splits)))
            return core.alltoall_async(
                _np(t), n, splits=splits,
                process_set_id=req.process_set_id)
        raise HorovodInternalError("unsupported op %s" % req.op_type)


class InProcessIciBackend(CollectiveBackend):
    """Single-controller SPMD plane: rank-major stacked inputs, the
    background engine fuses and executes compiled XLA collectives over
    the local mesh."""

    name = "inprocess_ici"

    def __init__(self, get_engine: Callable):
        self._get_engine = get_engine

    def enabled(self, req: OpRequest) -> bool:
        return req.op_type in DEVICE_OPS

    def _stack(self, tensor, ps_size):
        from .xla_ops import host_or_device, stack_rows
        if isinstance(tensor, (list, tuple)):
            arr = stack_rows(tensor)
        else:
            arr = host_or_device(tensor)
        if arr.shape[0] != ps_size:
            raise ValueError(
                "expected rank-major stacked input with leading dim %d "
                "(one slice per rank), got shape %s"
                % (ps_size, arr.shape))
        return arr

    def submit(self, req: OpRequest):
        from .engine import CollectiveHandle
        from .xla_ops import ADASUM, host_or_device, stack_rows
        eng = self._get_engine()
        if req.op_type == "allreduce":
            if req.red_op == ADASUM:
                from ..utils.adasum import adasum_reduce_stacked
                hs = []
                for t, n in zip(req.tensors, req.names):
                    h = CollectiveHandle(n)
                    try:
                        if eng._joined_member_indices(req.process_set_id):
                            # Zero rows are not a neutral element for
                            # Adasum's dot-product combine; reject
                            # rather than mis-reduce.
                            raise HorovodInternalError(
                                "Adasum allreduce submitted while ranks "
                                "are joined; only Sum/Average allreduce "
                                "supports zero-contribution join")
                        h._set_result(adasum_reduce_stacked(
                            self._stack(t, req.ps_size)))
                    except Exception as exc:  # noqa: BLE001
                        h._set_error(exc)
                    hs.append(h)
                return hs if req.is_group else hs[0]
            hs = [eng.enqueue_allreduce(
                n, self._stack(t, req.ps_size), req.red_op,
                req.prescale, req.postscale, req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        if req.op_type == "allgather":
            def one_allgather(t, n):
                if isinstance(t, (list, tuple)):
                    per_rank = [host_or_device(x) for x in t]
                    if len(per_rank) != req.ps_size:
                        raise ValueError("need one tensor per rank")
                else:
                    arr = host_or_device(t)
                    per_rank = [arr[r] for r in range(req.ps_size)]
                return eng.enqueue_allgather(n, per_rank,
                                             req.process_set_id)
            hs = [one_allgather(t, n)
                  for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        if req.op_type == "reducescatter":
            hs = [eng.enqueue_reducescatter(
                n, self._stack(t, req.ps_size), req.red_op,
                req.process_set_id)
                for t, n in zip(req.tensors, req.names)]
            return hs if req.is_group else hs[0]
        t, n = req.tensors[0], req.names[0]
        if req.op_type == "broadcast":
            return eng.enqueue_broadcast(
                n, self._stack(t, req.ps_size), req.root_rank,
                req.process_set_id)
        if req.op_type == "alltoall":
            splits = req.splits
            if isinstance(t, (list, tuple)):
                t = stack_rows(t) if splits is None \
                    else [host_or_device(x) for x in t]
            if splits is not None:
                splits = np.asarray(splits)
                if isinstance(t, list):
                    t = stack_rows(t) if len(
                        {x.shape for x in t}) == 1 else t
            return eng.enqueue_alltoall(n, t, splits, req.process_set_id)
        raise HorovodInternalError("unsupported op %s" % req.op_type)
