"""Core lifecycle + identity API: init / shutdown / rank / size / ...

Equivalent of the reference's ``horovod/common/basics.py``
(``HorovodBasics``) plus the init path of ``horovod/common/operations.cc``
(``InitializeHorovodOnce``): reads env config once, discovers topology
(TPU coords / launcher env instead of MPI), builds the process-set table
and the background collective engine, and exposes the identity calls every
adapter re-exports.

Controller modes (reference: MPI vs Gloo controller selection):

* ``inprocess`` — single-controller SPMD: ranks are mesh devices, the
  engine executes XLA collectives directly.  Default when no launcher env
  is present.  This is the TPU-idiomatic mode.
* ``tcp``       — one process per slot, rank-0 negotiation + host-side
  collectives over TCP through the native C++ core
  (``horovod_tpu/core``), bootstrap via the rendezvous KV server.  The
  Gloo-equivalent.  Selected automatically when the launcher exported
  ``HOROVOD_RANK``/``HOROVOD_SIZE``.
* ``multihost`` — one process per host, every process joined into one
  global JAX runtime (``jax.distributed``): the native core carries the
  control plane (negotiation/stall/elastic) while payloads execute as
  XLA collectives over the global mesh — ICI/DCN on pods.  The
  reference's MPI-control/NCCL-payload split (SURVEY §2.6), TPU-native.
  Select with ``--multihost`` on the launcher or
  ``HOROVOD_CONTROLLER=multihost``.
"""

from __future__ import annotations

import atexit
import contextlib
import logging
import threading
from typing import List, Optional, Sequence

from . import metrics, scopes
from . import process_sets as _ps
from .config import Config
from .topology import Topology, inprocess_topology, multiprocess_topology
from ..utils.timeline import get_timeline

LOG = logging.getLogger("horovod_tpu")

_LOG_LEVELS = {"trace": logging.DEBUG, "debug": logging.DEBUG,
               "info": logging.INFO, "warning": logging.WARNING,
               "error": logging.ERROR, "fatal": logging.CRITICAL,
               "off": logging.CRITICAL + 10}


class _GlobalState:
    """Singleton runtime state (reference: HorovodGlobalState)."""

    def __init__(self):
        self.initialized = False
        self.config: Optional[Config] = None
        self.topology: Optional[Topology] = None
        self.engine = None          # CollectiveEngine (inprocess mode)
        self.tcp_core = None        # native core handle (tcp/multihost)
        self.mh_engine = None       # MultihostEngine (multihost mode)
        self.op_manager = None      # backend priority walk (op manager)
        self.controller_mode = "inprocess"
        self.lock = threading.Lock()


_state = _GlobalState()


def _resolve_process_set_ranks(process_set_id: int) -> Optional[List[int]]:
    ps = _ps.process_set_by_id(process_set_id)
    return ps.ranks


def init(devices: Optional[Sequence] = None,
         process_sets: Optional[Sequence] = None,
         controller: Optional[str] = None,
         comm=None):
    """Initialize the runtime.  ``comm`` is accepted for reference API
    compatibility (an MPI communicator there) and must be None here.

    ``devices``: explicit jax device list for the world (defaults to all
    addressable devices).  ``process_sets``: ProcessSets (or rank lists) to
    register at init, like the reference's ``hvd.init(process_sets=...)``.
    """
    if comm is not None:
        raise ValueError(
            "MPI communicators do not exist on TPU; use process_sets or "
            "the launcher instead")
    import os
    if (os.environ.get("HOROVOD_ELASTIC_DRIVER_ADDR")
            and "HOROVOD_RANK" not in os.environ):
        # Elastic worker calling hvd.init() before the run decorator:
        # fetch a rank assignment from the elastic driver first.
        from ..elastic.worker import (install_assignment,
                                      notification_manager)
        nm = notification_manager()
        nm.init()
        install_assignment(nm.rendezvous())
    with _state.lock, contextlib.ExitStack() as spans:
        if _state.initialized:
            return
        # hvd.init: what follows, once a world; its children below are
        # where a slow start can hide (common/scopes.py, host spans).
        spans.enter_context(metrics.span(scopes.INIT))
        config = Config.from_env()
        logging.basicConfig()
        LOG.setLevel(_LOG_LEVELS.get(config.log_level, logging.WARNING))
        mode = (controller or config.controller or "auto").lower()
        if mode == "auto":
            mode = "tcp" if config.rank is not None else "inprocess"
        _state.config = config
        _state.controller_mode = mode

        timeline = get_timeline()
        if config.timeline:
            timeline.initialize(config.timeline, config.timeline_mark_cycles)

        import sys
        if mode != "tcp" or "jax" in sys.modules:
            # Before this process compiles anything.  A tcp worker
            # that never imports jax (host payloads only) is not made
            # to pay for the import here.
            from .device import place_compile_cache
            place_compile_cache()

        from ..utils import plancache

        def plan_bootstrap():
            # Collective-plan plane (persistent autotuned plans): fresh
            # state per init — an elastic re-init re-loads/adopts
            # against the (possibly resized) world's fingerprint.
            with metrics.span(scopes.INIT_PLAN):
                plancache.reset()
                plancache.bootstrap(config, _state.topology, mode)

        if mode == "inprocess":
            import jax
            from ..ops.engine import CollectiveEngine
            with metrics.span(scopes.INIT_DEVICES):
                # The runtime reaching the chip, when this is what first
                # touches it.
                devs = (list(devices) if devices is not None
                        else list(jax.devices()))
            _state.topology = inprocess_topology(devs)
            # Plan bootstrap BEFORE the engine: the cached tuned
            # operating point must land in config before the cycle
            # loop reads it.
            plan_bootstrap()
            with metrics.span(scopes.INIT_ENGINE):
                _state.engine = CollectiveEngine(
                    devs, config, timeline, _resolve_process_set_ranks)
            if config.autotune:
                from ..utils.autotune import ParameterManager
                _state.engine.parameter_manager = ParameterManager(
                    config.fusion_threshold_bytes, config.cycle_time_ms,
                    log_path=config.autotune_log,
                    warmup=config.autotune_warmup_samples,
                    steps_per_sample=config.autotune_steps_per_sample,
                    warm_start=plancache.tuned_warm_start())
        elif mode in ("tcp", "multihost"):
            from ..core.client import TcpCore
            _state.topology = multiprocess_topology(
                config.rank or 0, config.size or 1,
                config.local_rank, config.local_size,
                config.cross_rank, config.cross_size)
            if mode == "multihost":
                # Payload plane first: join the global JAX runtime so
                # jax.devices() spans the world before any mesh builds.
                from .multihost import init_jax_distributed
                with metrics.span(scopes.INIT_DEVICES):
                    init_jax_distributed(config, _state.topology.rank,
                                         _state.topology.size)
            # Plan bootstrap: rank 0 loads its cache and publishes to
            # the rendezvous KV; other members adopt the published
            # copy so every member routes identically (late joiners
            # and respawned workers warm-start from the pod's
            # best-known plan instead of re-tuning).
            plan_bootstrap()
            with metrics.span(scopes.INIT_ENGINE):
                _state.tcp_core = TcpCore(_state.topology, config)
                try:
                    _state.tcp_core.initialize()
                except BaseException:
                    # Elastic re-init can race a world change; release
                    # the half-bootstrapped core so a retry starts clean.
                    try:
                        _state.tcp_core.shutdown()
                    except Exception:  # noqa: BLE001
                        pass
                    _state.tcp_core = None
                    raise
                ws = plancache.tuned_warm_start()
                if ws is not None:
                    # Native warm start, NOT gated on config.autotune:
                    # the controller reads params_->fusion_threshold()
                    # every negotiation round whether or not the tuner
                    # samples, so a rerun with autotuning off still runs
                    # AT the cached operating point (the natural "reuse
                    # the tuned plan" rerun).  Rank 0's coordinator
                    # broadcasts the values; a harmless store on workers.
                    _state.tcp_core.autotune_warm_start(*ws)
                if mode == "multihost":
                    from ..ops.multihost import MultihostEngine
                    _state.mh_engine = MultihostEngine(
                        _state.tcp_core, config, timeline,
                        _resolve_process_set_ranks)
        else:
            raise ValueError("unknown controller mode %r" % mode)

        # Backend registry (reference operation_manager.cc): the walk
        # order per mode, overridable by env, extensible at runtime via
        # register_backend().
        from ..ops.op_manager import (HostTcpBackend, InProcessIciBackend,
                                      MultihostIciBackend, OpManager,
                                      order_from_env)
        if mode == "inprocess":
            backends = [InProcessIciBackend(_get_engine)]
        elif mode == "tcp":
            backends = [HostTcpBackend(_get_tcp_core)]
        else:  # multihost: device plane first, host plane fallback
            backends = [MultihostIciBackend(_get_mh_engine, _get_tcp_core),
                        HostTcpBackend(_get_tcp_core)]
        env_order = (os.environ.get("HVD_TPU_BACKENDS")
                     or os.environ.get("HOROVOD_BACKENDS"))
        if env_order:
            backends = order_from_env(backends, env_order)
        _state.op_manager = OpManager(backends)

        # Re-derive the registry against the NEW world instead of
        # wiping it: sets registered before an elastic resize survive
        # when their ranks still exist, and sets holding ranks beyond
        # the new world are dropped loudly (their ids detach so stale
        # handles raise instead of aliasing a recycled id).
        _ps.reset_registry(world_size=_state.topology.size
                           if _state.topology is not None else None)
        # Mark initialized BEFORE registering init-time process sets:
        # registration mirrors each set into the native core (tcp /
        # multihost modes), which the registry only does for an
        # initialized runtime.
        _state.initialized = True
        _ps.remirror_registered_sets()
        if process_sets:
            for ps in process_sets:
                # Idempotent across shutdown/re-init: registrations
                # survive the cycle, so a set that re-derived into the
                # new world is reused, not re-added (the duplicate-
                # ranks check would otherwise fail the second init).
                if _ps.registered_equivalent(ps) is None:
                    _ps.add_process_set(ps)
        atexit.register(shutdown)


def shutdown():
    """Tear down the background engine / native core (``hvd.shutdown``)."""
    with _state.lock:
        if not _state.initialized:
            return
        # Persist the collective-plan plane FIRST, while the live
        # tuners (in-process ParameterManager / native core) can still
        # be read: the merged plan (per-class decisions + tuned point
        # + flash blocks) is what the next run warm-starts from.
        from ..utils import plancache
        plancache.finalize(tcp_core=_state.tcp_core,
                           engine=_state.engine)
        if _state.engine is not None:
            _state.engine.shutdown()
            _state.engine = None
        if _state.mh_engine is not None:
            _state.mh_engine.shutdown()
            _state.mh_engine = None
        if _state.tcp_core is not None:
            _state.tcp_core.shutdown()
            _state.tcp_core = None
        _state.op_manager = None
        if _state.controller_mode == "multihost":
            # Leave the global JAX runtime so an elastic re-init can
            # rejoin a (possibly resized) world cleanly.
            from .multihost import shutdown_jax_distributed
            shutdown_jax_distributed()
        get_timeline().shutdown()
        # The registry SURVIVES shutdown (its core mirrors died with
        # the core): an elastic resize is shutdown()+init(), and the
        # next init re-derives every registration against the new
        # world, dropping dangling sets loudly and re-mirroring the
        # survivors into the fresh core.
        _state.initialized = False
        _state.topology = None


def is_initialized() -> bool:
    return _state.initialized


def _require_init():
    if not _state.initialized:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call hvd.init() first")


def _controller_is_spmd() -> bool:
    return _state.controller_mode == "inprocess"


def _get_engine():
    _require_init()
    if _state.engine is None:
        raise RuntimeError(
            "eager collectives in tcp mode go through the native core")
    return _state.engine


def _get_tcp_core():
    _require_init()
    return _state.tcp_core


def _get_mh_engine():
    _require_init()
    if _state.mh_engine is None:
        raise RuntimeError("not in multihost mode")
    return _state.mh_engine


def _controller_mode() -> str:
    return _state.controller_mode


def _get_op_manager():
    _require_init()
    return _state.op_manager


def register_backend(backend, index: int = 0):
    """Insert a custom collective backend at priority ``index`` in the
    op-manager walk (reference: adding an entry to
    ``operation_manager.cc``'s priority list).  The backend sees every
    eager collective as an ``OpRequest`` and may accept or decline
    per-tensor via ``enabled()``."""
    _require_init()
    _state.op_manager.register(backend, index)


def _get_config() -> Config:
    _require_init()
    return _state.config


def rank() -> int:
    _require_init()
    return _state.topology.rank


def size() -> int:
    _require_init()
    return _state.topology.size


def local_rank() -> int:
    _require_init()
    return _state.topology.local_rank


def local_size() -> int:
    _require_init()
    return _state.topology.local_size


def cross_rank() -> int:
    _require_init()
    return _state.topology.cross_rank


def cross_size() -> int:
    _require_init()
    return _state.topology.cross_size


def is_homogeneous() -> bool:
    _require_init()
    return _state.topology.is_homogeneous()


def topology() -> Topology:
    _require_init()
    return _state.topology


def start_timeline(file_path: str, mark_cycles: bool = False):
    """Begin writing the chrome-trace timeline (``hvd.start_timeline``)."""
    get_timeline().initialize(file_path, mark_cycles)


def stop_timeline():
    get_timeline().shutdown()


# -- capability probes (reference: *_built()/*_enabled() in basics.py) ----

def xla_built() -> bool:
    return True


def tcp_built() -> bool:
    try:
        from ..core.client import core_library_available
        return core_library_available()
    except Exception:
        return False


def gloo_built() -> bool:
    # The TCP core is this framework's Gloo-equivalent CPU path.
    return tcp_built()


def mpi_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False
