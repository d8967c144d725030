"""horovod_tpu: a TPU-native distributed training framework with the
capability surface of Horovod (reference: aaron276h/horovod).

Data-parallel collectives (allreduce / grouped_allreduce / allgather /
broadcast / alltoall / reducescatter / join / barrier) behind
``init``/``rank``/``size`` and ``DistributedOptimizer``-style adapters,
executed as XLA collectives over ICI/DCN via PJRT instead of
NCCL/MPI/Gloo.  Usage mirrors the reference::

    import horovod_tpu as hvd           # or: import horovod_tpu.jax as hvd
    hvd.init()
    avg = hvd.allreduce(grads, op=hvd.Average)

See SURVEY.md for the architecture map against the reference tree.

The top-level namespace resolves lazily (PEP 562), like the reference's
slim ``horovod/__init__.py``: importing the package must not pull jax,
so launcher-only hosts (``python -m horovod_tpu.runner``, including
``--check-build`` on a machine without any framework) work framework-
free.
"""

import time as _time

# Where hvd.import starts (epoch, perf_counter); horovod_tpu/jax/__init__.py
# ends it.  Nothing heavier than the clock may be imported up here.
_IMPORT_STARTED = (_time.time(), _time.perf_counter())

__version__ = "0.1.0"

# name -> (module, attr); attr None re-exports the symbol name itself.
_EXPORTS = {}
for _mod, _names in (
    (".common.basics",
     ("init", "shutdown", "is_initialized", "rank", "size", "local_rank",
      "local_size", "cross_rank", "cross_size", "is_homogeneous",
      "topology", "start_timeline", "stop_timeline", "xla_built",
      "tcp_built", "gloo_built", "mpi_built", "nccl_built", "ccl_built",
      "ddl_built", "cuda_built", "rocm_built", "mpi_enabled",
      "mpi_threads_supported", "register_backend")),
    (".ops.op_manager", ("CollectiveBackend", "OpRequest")),
    (".common.process_sets",
     ("ProcessSet", "global_process_set", "add_process_set",
      "remove_process_set", "process_set_by_id", "process_set_ids")),
    (".ops.api",
     ("SUM", "AVERAGE", "MIN", "MAX", "PRODUCT", "ADASUM", "allreduce",
      "allreduce_async", "grouped_allreduce", "grouped_allreduce_async",
      "allgather", "allgather_async", "grouped_allgather",
      "grouped_allgather_async", "broadcast", "broadcast_async",
      "alltoall", "alltoall_async", "reducescatter",
      "reducescatter_async", "grouped_reducescatter",
      "grouped_reducescatter_async", "barrier", "join", "synchronize",
      "poll")),
    (".ops.engine", ("CollectiveHandle", "HorovodInternalError")),
    # Metrics plane: the live in-process snapshot (works without init —
    # the registry is process-local and always on).
    (".common.metrics", ("metrics_snapshot", "span_records")),
):
    for _n in _names:
        _EXPORTS[_n] = (_mod, _n)

# Reference-style aliases (horovod exposes mpi_ops.Sum etc. as hvd.Sum).
for _alias, _target in (("Sum", "SUM"), ("Average", "AVERAGE"),
                        ("Min", "MIN"), ("Max", "MAX"),
                        ("Product", "PRODUCT"), ("Adasum", "ADASUM")):
    _EXPORTS[_alias] = (".ops.api", _target)

__all__ = sorted(_EXPORTS) + ["__version__"]


def __getattr__(name):
    try:
        mod_name, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            "module %r has no attribute %r" % (__name__, name)) from None
    import importlib
    value = getattr(importlib.import_module(mod_name, __name__), attr)
    globals()[name] = value  # cache: subsequent lookups skip __getattr__
    return value


def __dir__():
    return __all__
