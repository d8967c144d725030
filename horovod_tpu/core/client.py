"""ctypes client for the native coordination core.

Counterpart of the reference's ``horovod/common/basics.py`` loading the
compiled shared library: builds ``libhvdtpu_core.so`` on demand (plain
``make``, no third-party deps), then drives the C API
(``hvd_tcp_init`` / ``hvd_tcp_enqueue`` / handle polling) for the
multi-process (one process per slot) world.
"""

from __future__ import annotations

import ctypes
import os
import pickle
import subprocess
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

_CORE_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_CORE_DIR, "libhvdtpu_core.so")

# Enum values must match src/common.h.
_DTYPES = {
    np.dtype("uint8"): 0, np.dtype("int8"): 1, np.dtype("uint16"): 2,
    np.dtype("int16"): 3, np.dtype("int32"): 4, np.dtype("int64"): 5,
    np.dtype("float16"): 6, np.dtype("float32"): 7,
    np.dtype("float64"): 8, np.dtype("bool"): 9,
}
try:  # bf16 wire format (the TPU-native low-precision dtype).
    import ml_dtypes
    _DTYPES[np.dtype(ml_dtypes.bfloat16)] = 10
except ImportError:  # pragma: no cover
    pass
_OP_TYPES = {"allreduce": 0, "allgather": 1, "broadcast": 2, "alltoall": 3,
             "reducescatter": 4, "barrier": 5, "join": 6}
_RED_OPS = {"Sum": 0, "Average": 1, "Min": 2, "Max": 3, "Product": 4,
            "Adasum": 5}

_build_lock = threading.Lock()


def build_library(force: bool = False) -> str:
    """Compile the core if the .so is missing or older than its
    sources; ``force=True`` rebuilds it from ``src/`` whatever is on
    disk (``make clean`` first: objects copied from another machine
    carry mtimes that mean nothing here).  A machine without a
    compiler fails here, loudly.

    ``HVD_TPU_CORE_LIB`` overrides the library outright (no build):
    the sanitizer test nodes compile ``make SANITIZE=thread`` side
    builds and point every spawned worker here, and ``xla_ops``
    exports the same variable so the XLA custom-call dlopens the very
    library the Python runtime initialized.
    """
    override = os.environ.get("HVD_TPU_CORE_LIB")
    if override:
        if not os.path.exists(override):
            raise FileNotFoundError(
                "HVD_TPU_CORE_LIB points at a missing library: %r"
                % override)
        return override
    with _build_lock:
        src_dir = os.path.join(_CORE_DIR, "src")
        if not force and os.path.exists(_LIB_PATH):
            lib_mtime = os.path.getmtime(_LIB_PATH)
            stale = any(
                os.path.getmtime(os.path.join(src_dir, f)) > lib_mtime
                for f in os.listdir(src_dir))
            if not stale:
                return _LIB_PATH
        for target in (["clean"], []) if force else ([],):
            proc = subprocess.run(
                ["make", "-j", "-s", *target], cwd=_CORE_DIR,
                capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(
                    "building the native core failed (make %s, rc=%d):"
                    "\n%s" % (" ".join(target), proc.returncode,
                              proc.stderr[-2000:]))
        return _LIB_PATH


def core_library_available() -> bool:
    try:
        build_library()
        return True
    except Exception:
        return False


_lib = None


def load_library():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(build_library())
    lib.hvd_tcp_init.argtypes = [ctypes.c_int, ctypes.c_int,
                                 ctypes.c_char_p]
    lib.hvd_tcp_init.restype = ctypes.c_int
    lib.hvd_tcp_enqueue.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_uint, ctypes.c_double,
        ctypes.c_double, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int]
    lib.hvd_tcp_enqueue.restype = ctypes.c_int
    lib.hvd_tcp_poll.argtypes = [ctypes.c_int]
    lib.hvd_tcp_poll.restype = ctypes.c_int
    lib.hvd_tcp_result_nbytes.argtypes = [ctypes.c_int]
    lib.hvd_tcp_result_nbytes.restype = ctypes.c_longlong
    lib.hvd_tcp_result_ndim.argtypes = [ctypes.c_int]
    lib.hvd_tcp_result_ndim.restype = ctypes.c_int
    lib.hvd_tcp_result_dims.argtypes = [ctypes.c_int,
                                        ctypes.POINTER(ctypes.c_longlong)]
    lib.hvd_tcp_recv_splits.argtypes = [ctypes.c_int,
                                        ctypes.POINTER(ctypes.c_longlong)]
    lib.hvd_tcp_recv_splits.restype = ctypes.c_int
    lib.hvd_tcp_copy_result.argtypes = [ctypes.c_int, ctypes.c_void_p]
    lib.hvd_tcp_copy_result.restype = ctypes.c_int
    lib.hvd_tcp_error_string.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                         ctypes.c_int]
    lib.hvd_tcp_error_string.restype = ctypes.c_int
    lib.hvd_tcp_release.argtypes = [ctypes.c_int]
    lib.hvd_tcp_add_process_set.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.hvd_tcp_add_process_set.restype = ctypes.c_uint
    lib.hvd_tcp_remove_process_set.argtypes = [ctypes.c_uint]
    lib.hvd_tcp_register_group.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int]
    lib.hvd_tcp_register_group.restype = ctypes.c_int
    lib.hvd_tcp_join.restype = ctypes.c_int
    lib.hvd_tcp_cache_hits.restype = ctypes.c_longlong
    lib.hvd_tcp_cache_misses.restype = ctypes.c_longlong
    lib.hvd_tcp_enqueue_external.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_uint, ctypes.c_double,
        ctypes.c_double, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int]
    lib.hvd_tcp_enqueue_external.restype = ctypes.c_int
    lib.hvd_tcp_next_negotiated.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.hvd_tcp_next_negotiated.restype = ctypes.c_int
    lib.hvd_tcp_wait_negotiated.argtypes = [ctypes.c_char_p,
                                            ctypes.c_int, ctypes.c_int]
    lib.hvd_tcp_wait_negotiated.restype = ctypes.c_int
    lib.hvd_tcp_external_done.argtypes = [ctypes.c_int, ctypes.c_int,
                                          ctypes.c_char_p]
    lib.hvd_tcp_autotune_observe.argtypes = [ctypes.c_ulonglong,
                                             ctypes.c_double]
    lib.hvd_tcp_autotune_observe.restype = None
    # A library that lacks any symbol below does not match src/ and
    # fails here, at load (ctypes names the symbol).
    lib.hvd_tcp_autotune_warm_start.argtypes = [ctypes.c_ulonglong,
                                                ctypes.c_double,
                                                ctypes.c_int]
    lib.hvd_tcp_autotune_warm_start.restype = None
    lib.hvd_tcp_autotune_state.argtypes = [
        ctypes.POINTER(ctypes.c_ulonglong),
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.hvd_tcp_autotune_state.restype = None
    lib.hvd_tcp_set_fastpath.argtypes = [ctypes.c_int]
    lib.hvd_tcp_set_fastpath.restype = None
    lib.hvd_tcp_fastpath_idle_rounds.argtypes = []
    lib.hvd_tcp_fastpath_idle_rounds.restype = ctypes.c_ulonglong
    lib.hvd_tcp_stopped.argtypes = []
    lib.hvd_tcp_stopped.restype = ctypes.c_int
    lib.hvd_tcp_kernel_tune_record.argtypes = [ctypes.c_int,
                                               ctypes.c_double]
    lib.hvd_tcp_kernel_tune_record.restype = None
    lib.hvd_tcp_kernel_tune_best.argtypes = []
    lib.hvd_tcp_kernel_tune_best.restype = ctypes.c_int
    lib.hvd_tcp_kernel_tune_samples.argtypes = []
    lib.hvd_tcp_kernel_tune_samples.restype = ctypes.c_int
    _lib = lib
    return lib


_OP_NAMES = {v: k for k, v in _OP_TYPES.items()}
_RED_NAMES = {v: k for k, v in _RED_OPS.items()}
_DTYPE_BY_ID = {v: k for k, v in _DTYPES.items()}


def parse_negotiated_record(rec: bytes) -> dict:
    """Decode one negotiated-group record emitted by the core's
    external-payload path (operations.cc CoreState::PerformOperation):
    op/dtype/reduce-op/root/process-set/scales + response aux sizes +
    (name, handle) per member entry, in fused order."""
    import struct
    off = 0

    def u8():
        nonlocal off
        v = rec[off]
        off += 1
        return v

    def u32():
        nonlocal off
        v = struct.unpack_from("<I", rec, off)[0]
        off += 4
        return v

    def i64():
        nonlocal off
        v = struct.unpack_from("<q", rec, off)[0]
        off += 8
        return v

    def f64():
        nonlocal off
        v = struct.unpack_from("<d", rec, off)[0]
        off += 8
        return v

    def s():
        nonlocal off
        n = u32()
        v = rec[off:off + n].decode()
        off += n
        return v

    g = {
        "op_type": _OP_NAMES[u8()],
        "dtype": _DTYPE_BY_ID[u8()],
        "red_op": _RED_NAMES[u8()],
        "root_rank": u32(),
        "process_set_id": u32(),
        "prescale": f64(),
        "postscale": f64(),
    }
    g["aux_sizes"] = [i64() for _ in range(u32())]
    g["entries"] = [{"name": s(), "handle": i64()} for _ in range(u32())]
    # Trailing fail-fast field: non-empty when the core refused to
    # zero-fill (a negotiated entry was missing on this non-joined
    # rank); the executor error-completes the group and poisons the
    # engine instead of running the record.
    g["error"] = s() if off < len(rec) else ""
    return g


def _marshal_dims(shape: Sequence[int]):
    shape = tuple(int(d) for d in shape)
    return ((ctypes.c_longlong * max(len(shape), 1))(*(shape or (0,))),
            len(shape))


def _marshal_splits(splits):
    if splits is None:
        return None, 0
    return ((ctypes.c_longlong * len(splits))(*[int(s) for s in splits]),
            len(splits))


class TcpHandle:
    """Async handle over the native core (mirrors CollectiveHandle)."""

    def __init__(self, lib, handle: int, dtype, name: str):
        self._lib = lib
        self._h = handle
        self._dtype = dtype
        self.name = name

    def poll(self) -> bool:
        return self._lib.hvd_tcp_poll(self._h) != 0

    def wait(self, timeout: Optional[float] = None):
        deadline = time.monotonic() + (timeout or 3600.0)
        while True:
            st = self._lib.hvd_tcp_poll(self._h)
            if st == 1:
                return self._fetch()
            if st == 2:
                buf = ctypes.create_string_buffer(4096)
                self._lib.hvd_tcp_error_string(self._h, buf, 4096)
                self._lib.hvd_tcp_release(self._h)
                from ..ops.engine import HorovodInternalError
                raise HorovodInternalError(buf.value.decode())
            if time.monotonic() > deadline:
                raise TimeoutError("collective %r timed out" % self.name)
            time.sleep(0.0005)

    def _fetch(self):
        lib = self._lib
        ndim = lib.hvd_tcp_result_ndim(self._h)
        dims = (ctypes.c_longlong * max(ndim, 1))()
        if ndim > 0:
            lib.hvd_tcp_result_dims(self._h, dims)
        shape = tuple(dims[i] for i in range(ndim))
        out = np.empty(shape, dtype=self._dtype)
        if out.size:
            rc = lib.hvd_tcp_copy_result(
                self._h, out.ctypes.data_as(ctypes.c_void_p))
            if rc != 0:
                from ..ops.engine import HorovodInternalError
                raise HorovodInternalError("result copy failed")
        # Count query first (null buffer), then an exact-size fetch —
        # no fixed cap, so pod-scale worlds can't silently truncate.
        nsp = lib.hvd_tcp_recv_splits(self._h, None)
        recv_splits: List[int] = []
        if nsp > 0:
            splits = (ctypes.c_longlong * nsp)()
            lib.hvd_tcp_recv_splits(self._h, splits)
            recv_splits = [int(splits[i]) for i in range(nsp)]
        lib.hvd_tcp_release(self._h)
        return (out, recv_splits) if recv_splits else out


class TcpCore:
    """Multi-process backend bound to the launcher's env (HOROVOD_RANK /
    HOROVOD_SIZE / rendezvous address table)."""

    def __init__(self, topology, config):
        self.topology = topology
        self.config = config
        self._lib = None
        # process-set id -> member count (id 0 is the world); used to
        # split uniform alltoalls by the SET size, not the world size
        self._ps_sizes = {0: topology.size}
        self._poll_buf = None  # reusable next_negotiated buffer

    # -- lifecycle ---------------------------------------------------------

    def initialize(self):
        self._lib = load_library()
        self._ps_sizes = {0: self.topology.size}
        addrs = self._resolve_addrs()
        rc = self._lib.hvd_tcp_init(
            self.topology.rank, self.topology.size,
            ";".join(addrs).encode())
        if rc != 0:
            raise RuntimeError("native core init failed (rank %d)"
                               % self.topology.rank)

    def _resolve_addrs(self) -> List[str]:
        """Address table: direct env (HOROVOD_ADDRS) or rendezvous KV."""
        direct = os.environ.get("HOROVOD_ADDRS")
        if direct:
            return direct.split(";")
        addr = self.config.rendezvous_addr
        if not addr:
            # Single host default: sequential ports from a base.
            base = int(os.environ.get("HOROVOD_PORT_BASE", "29600"))
            return ["127.0.0.1:%d" % (base + r)
                    for r in range(self.topology.size)]
        from ..runner.http_client import RendezvousClient
        client = RendezvousClient(addr, secret=self.config.secret_key)
        port = int(os.environ.get("HOROVOD_PORT_BASE", "29600")) + \
            self.topology.rank
        my = "%s:%d" % (os.environ.get("HOROVOD_HOSTNAME", "127.0.0.1"),
                        port)
        client.put("addr/%d" % self.topology.rank, my)
        addrs = []
        for r in range(self.topology.size):
            addrs.append(client.get_blocking("addr/%d" % r, timeout=60.0))
        return addrs

    def shutdown(self):
        if self._lib is None:
            return
        self._lib.hvd_tcp_request_shutdown()
        self._lib.hvd_tcp_wait_shutdown()

    # -- collectives -------------------------------------------------------

    def _enqueue(self, name, op_type, arr: Optional[np.ndarray],
                 red_op="Sum", root_rank=0, process_set_id=0,
                 prescale=1.0, postscale=1.0, splits=None) -> TcpHandle:
        if arr is not None:
            arr = np.ascontiguousarray(arr)
            dims, ndim = _marshal_dims(arr.shape)
            data = arr.ctypes.data_as(ctypes.c_void_p)
            dtype_id = _DTYPES[arr.dtype]
            dtype = arr.dtype
        else:
            dims, ndim = _marshal_dims(())
            data = None
            dtype_id = 0
            dtype = np.dtype("uint8")
        sp, nsp = _marshal_splits(splits)
        h = self._lib.hvd_tcp_enqueue(
            name.encode(), _OP_TYPES[op_type], data, dims, ndim, dtype_id,
            _RED_OPS[red_op], root_rank, process_set_id, prescale,
            postscale, sp, nsp)
        if h < 0:
            raise RuntimeError("enqueue failed for %r" % name)
        return TcpHandle(self._lib, h, dtype, name)

    def allreduce_async(self, arr, name, op="Sum", prescale=1.0,
                        postscale=1.0, process_set_id=0):
        return self._enqueue(name, "allreduce", arr, red_op=op,
                             prescale=prescale, postscale=postscale,
                             process_set_id=process_set_id)

    def allgather_async(self, arr, name, process_set_id=0):
        return self._enqueue(name, "allgather", arr,
                             process_set_id=process_set_id)

    def broadcast_async(self, arr, name, root_rank=0, process_set_id=0):
        return self._enqueue(name, "broadcast", arr, root_rank=root_rank,
                             process_set_id=process_set_id)

    def alltoall_async(self, arr, name, splits=None, process_set_id=0):
        if splits is None:
            n = self._ps_sizes.get(process_set_id, self.topology.size)
            if arr.shape[0] % n:
                raise ValueError(
                    "uniform alltoall needs dim0 %% set size (%d) == 0"
                    % n)
            splits = [arr.shape[0] // n] * n
        return self._enqueue(name, "alltoall", arr, splits=splits,
                             process_set_id=process_set_id)

    def reducescatter_async(self, arr, name, op="Sum", process_set_id=0):
        return self._enqueue(name, "reducescatter", arr, red_op=op,
                             process_set_id=process_set_id)

    # -- external-payload (device collective) protocol ---------------------

    def enqueue_external(self, name, op_type, shape, dtype, red_op="Sum",
                         root_rank=0, process_set_id=0, prescale=1.0,
                         postscale=1.0, splits=None) -> TcpHandle:
        """Negotiate order/readiness only; the payload executes as an XLA
        collective driven by the multihost engine (``ops/multihost.py``)."""
        dims, ndim = _marshal_dims(shape)
        sp, nsp = _marshal_splits(splits)
        h = self._lib.hvd_tcp_enqueue_external(
            name.encode(), _OP_TYPES[op_type], dims, ndim,
            _DTYPES[np.dtype(dtype)], _RED_OPS[red_op], root_rank,
            process_set_id, prescale, postscale, sp, nsp)
        if h < 0:
            raise RuntimeError("external enqueue failed for %r" % name)
        return TcpHandle(self._lib, h, np.dtype(dtype), name)

    def next_negotiated(self) -> Optional[bytes]:
        """Pop the next negotiated device-payload group record (response
        order — identical on every rank), or None when none is pending."""
        # One reusable buffer: the executor polls this in a tight loop
        # where the common answer is "nothing pending".
        if self._poll_buf is None:
            self._poll_buf = ctypes.create_string_buffer(1 << 16)
        n = self._lib.hvd_tcp_next_negotiated(self._poll_buf,
                                              len(self._poll_buf))
        if n < 0:  # record larger than the buffer: grow and retry
            self._poll_buf = ctypes.create_string_buffer(-n)
            n = self._lib.hvd_tcp_next_negotiated(self._poll_buf,
                                                  len(self._poll_buf))
        if n <= 0:
            return None
        return self._poll_buf.raw[:n]

    def wait_negotiated(self, timeout_ms: int) -> Optional[bytes]:
        """Like :meth:`next_negotiated` but blocks in the core up to
        ``timeout_ms`` for a record — the executor wakes the instant
        negotiation finishes instead of poll-sleeping."""
        if self._poll_buf is None:
            self._poll_buf = ctypes.create_string_buffer(1 << 16)
        n = self._lib.hvd_tcp_wait_negotiated(
            self._poll_buf, len(self._poll_buf), int(timeout_ms))
        if n < 0:  # record larger than the buffer: grow and retry
            self._poll_buf = ctypes.create_string_buffer(-n)
            n = self._lib.hvd_tcp_next_negotiated(self._poll_buf,
                                                  len(self._poll_buf))
        if n <= 0:
            return None
        return self._poll_buf.raw[:n]

    def stopped(self) -> bool:
        """True once the background loop aborted (negotiation failure /
        peer disconnect): pending work was failed core-side and no
        further cycles will run."""
        return bool(self._lib.hvd_tcp_stopped())

    def external_done(self, handle: int, ok: bool = True,
                      error: str = ""):
        self._lib.hvd_tcp_external_done(handle, 1 if ok else 0,
                                        error.encode())

    def autotune_observe(self, nbytes: int, secs: float):
        """Report a device-plane allreduce group's (bytes, time-to-
        completion) to rank 0's autotuner (no-op elsewhere)."""
        self._lib.hvd_tcp_autotune_observe(int(nbytes), float(secs))

    def set_fastpath(self, on: bool):
        """Stretch (on) / restore (off) the background loop's idle
        negotiation cadence while the engine's frozen schedule makes
        rounds pointless."""
        self._lib.hvd_tcp_set_fastpath(1 if on else 0)

    def fastpath_idle_rounds(self) -> int:
        """Negotiation rounds the core skipped (stretched) while the
        fast path was on, for levers.fastpath attribution."""
        return int(self._lib.hvd_tcp_fastpath_idle_rounds())

    def autotune_warm_start(self, fusion_threshold: int,
                            cycle_time_ms: float, converged: bool):
        """Adopt a persisted plan's tuned operating point (plan-cache
        warm start): converged plans freeze the rank-0 tuner at the
        point; unconverged ones resume sampling there with a single
        warm-up cycle left."""
        self._lib.hvd_tcp_autotune_warm_start(
            int(fusion_threshold), float(cycle_time_ms),
            1 if converged else 0)

    def autotune_state(self) -> dict:
        """Native tuner snapshot for plan persistence."""
        fn = self._lib.hvd_tcp_autotune_state
        fusion = ctypes.c_ulonglong()
        cycle = ctypes.c_double()
        converged = ctypes.c_int()
        samples = ctypes.c_int()
        warmup = ctypes.c_int()
        fn(ctypes.byref(fusion), ctypes.byref(cycle),
           ctypes.byref(converged), ctypes.byref(samples),
           ctypes.byref(warmup))
        return {"fusion_threshold": int(fusion.value),
                "cycle_time_ms": float(cycle.value),
                "converged": bool(converged.value),
                "samples": int(samples.value),
                "warmup_left": int(warmup.value)}

    def kernel_tune_record(self, choice: int, score: float):
        """Report one kernel-parameter sample (flash block-shape sweep)
        to the core's KernelTuner — the native twin of
        utils.autotune.KernelBlockTuner."""
        self._lib.hvd_tcp_kernel_tune_record(int(choice), float(score))

    def kernel_tune_best(self) -> int:
        """Argmax-by-mean choice index; -1 before any sample."""
        return int(self._lib.hvd_tcp_kernel_tune_best())

    def kernel_tune_samples(self) -> int:
        return int(self._lib.hvd_tcp_kernel_tune_samples())

    def barrier(self, name=None, process_set_id=0):
        h = self._enqueue(name or "barrier.%f" % time.monotonic(),
                          "barrier",
                          np.zeros((1,), np.uint8),
                          process_set_id=process_set_id)
        h.wait()

    def join(self) -> int:
        lib = self._lib
        h = lib.hvd_tcp_join()
        handle = TcpHandle(lib, h, np.dtype("int64"), "__join__")
        out = handle.wait()
        return int(np.asarray(out).reshape(-1)[0]) if np.size(out) else -1

    # -- object helpers ----------------------------------------------------

    def broadcast_object(self, obj, root_rank=0, name=None):
        name = name or "broadcast_object"
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        size_arr = np.array([payload.size], dtype=np.int64)
        sz = self.broadcast_async(size_arr, name + ".size",
                                  root_rank=root_rank).wait()
        n = int(np.asarray(sz).reshape(-1)[0])
        if self.topology.rank != root_rank:
            payload = np.zeros((n,), dtype=np.uint8)
        out = self.broadcast_async(payload, name + ".data",
                                   root_rank=root_rank).wait()
        return pickle.loads(np.asarray(out).tobytes())

    def allgather_object(self, obj, name=None):
        name = name or "allgather_object"
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        sizes = self.allgather_async(
            np.array([payload.size], dtype=np.int64),
            name + ".sizes").wait()
        blob = self.allgather_async(payload, name + ".data").wait()
        blob = np.asarray(blob)
        out, off = [], 0
        for s in np.asarray(sizes).reshape(-1):
            out.append(pickle.loads(blob[off:off + int(s)].tobytes()))
            off += int(s)
        return out

    def add_process_set(self, ranks: Sequence[int]) -> int:
        arr = (ctypes.c_int * len(ranks))(*[int(r) for r in ranks])
        ps_id = int(self._lib.hvd_tcp_add_process_set(arr, len(ranks)))
        self._ps_sizes[ps_id] = len(ranks)
        return ps_id

    def register_group(self, names: Sequence[str]) -> int:
        arr = (ctypes.c_char_p * len(names))(
            *[n.encode() for n in names])
        return int(self._lib.hvd_tcp_register_group(arr, len(names)))

    def cache_stats(self):
        return (int(self._lib.hvd_tcp_cache_hits()),
                int(self._lib.hvd_tcp_cache_misses()))
