"""``horovod_tpu.jax`` — the JAX adapter (the reference's per-framework
adapter pattern, e.g. ``horovod/torch/__init__.py``, applied to JAX; the
``horovod.jax`` adapter named by BASELINE.json's north star).

    import horovod_tpu.jax as hvd
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
"""

# Identity / lifecycle / eager collectives re-exported from the core.
from ..common.basics import (init, shutdown, is_initialized, rank, size,
                             local_rank, local_size, cross_rank, cross_size,
                             is_homogeneous, topology, start_timeline,
                             stop_timeline, xla_built, tcp_built, gloo_built,
                             mpi_built, nccl_built, ccl_built, ddl_built,
                             cuda_built, rocm_built, mpi_enabled,
                             mpi_threads_supported)
from ..common.metrics import metrics_snapshot, span_records
from ..common.process_sets import (ProcessSet, global_process_set,
                                   add_process_set, remove_process_set,
                                   process_set_by_id, process_set_ids)
from ..ops.api import (SUM, AVERAGE, MIN, MAX, PRODUCT, ADASUM,
                       allreduce, allreduce_async, grouped_allreduce,
                       grouped_allreduce_async, allgather, allgather_async,
                       grouped_allgather, grouped_allgather_async,
                       broadcast, broadcast_async, alltoall, alltoall_async,
                       reducescatter, reducescatter_async,
                       grouped_reducescatter, grouped_reducescatter_async,
                       barrier, join, synchronize, poll)
from ..ops.engine import CollectiveHandle, HorovodInternalError

# Adapter-specific surface.
from .compression import Compression
from .optimizer import (DistributedOptimizer, DistributedGradientTape,
                        allreduce_gradients)
from .functions import (broadcast_parameters, broadcast_optimizer_state,
                        broadcast_object, allgather_object)
from .sync_batch_norm import (SyncBatchNorm, sync_batch_norm_stats,
                              sync_batch_norm_apply)
from .data_parallel import (fetch,
                            make_data_parallel_step, make_sharded_jit_step,
                            shard_batch, replicate, metric_average)
from .zero import (make_zero1_step, make_zero2_step, make_zero3_step,
                   make_zero_step, zero_stage_from_env)
from .mesh import create_mesh, create_hybrid_mesh
from . import spmd
from . import callbacks
from .. import elastic

Sum = SUM
Average = AVERAGE
Min = MIN
Max = MAX
Product = PRODUCT
Adasum = ADASUM


def _record_import():
    """``hvd.import``: the package's first line to here, once a process (a
    record and an observation; too early to be a profiler annotation)."""
    import time

    from .. import _IMPORT_STARTED
    from ..common import metrics, scopes
    epoch, t0 = _IMPORT_STARTED
    metrics.record_span(scopes.IMPORT, epoch,
                        epoch + time.perf_counter() - t0)


_record_import()
del _record_import
