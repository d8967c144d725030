"""Data-parallel step builders: the idiomatic-TPU training loop.

The reference wires distribution into the optimizer because torch/TF
execute op-by-op.  Under XLA the natural unit is the whole compiled train
step, so this module provides the two TPU-native ways to run DP:

* ``make_data_parallel_step`` — explicit SPMD via ``jax.shard_map`` over
  the 'hvd' mesh axis: per-device batch shard in, psum-averaged gradients
  (through ``DistributedOptimizer``) in-program, one ``psum`` a gradient
  and no packing; XLA combines them and the reduce rides ICI.  On the v5e
  an all-reduce holds the op stream for as long as it runs, whether it
  sits inside the backward pass or behind it (PERF.md, PR 26), so the
  step is compiled with the compiler's own combining: one all-reduce of
  every gradient, after the last.
* ``make_sharded_jit_step`` — compiler-driven: params replicated, batch
  sharded; ``jax.jit`` with those shardings makes XLA insert the gradient
  all-reduce itself.  Zero framework code in the hot path — the ceiling
  case the engine's eager path is measured against.

``shard_batch`` places a host batch so dim 0 is split across the world.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common import basics, metrics, scopes
from ..ops.xla_ops import AVERAGE, host_or_device
from . import spmd
from .compression import Compression
from .optimizer import DistributedOptimizer


_flat_mesh_cache = {}


def _multihost() -> bool:
    return (basics.is_initialized()
            and basics._controller_mode() == "multihost")


def _world_mesh():
    """The DP mesh: the in-process engine's device mesh, or — in
    multihost mode — ONE flat axis over every device of every process
    (the global mesh ``jax.distributed`` assembled), so the same step
    builders drive a pod the way they drive a single host."""
    if _multihost():
        import collections

        from jax.sharding import Mesh
        devs = sorted(jax.devices(),
                      key=lambda d: (d.process_index, d.id))
        counts = collections.Counter(d.process_index for d in devs)
        if len(set(counts.values())) > 1:
            # ValueError, NOT HorovodInternalError: the elastic wrapper
            # retries HorovodInternalError, and a heterogeneous slice
            # does not heal by re-rendezvousing into the same hosts —
            # this must terminate the run with the actionable message.
            raise ValueError(
                "multihost data parallelism needs EQUAL addressable-"
                "device counts on every process, got %s per process. "
                "shard_batch/make_data_parallel_step assume uniform "
                "per-process shards; rebalance the slice (or resize "
                "the elastic world to homogeneous hosts) before "
                "building the step."
                % dict(sorted(counts.items())))
        # Key by the device identities so an elastic re-init with a
        # changed world never reuses a stale mesh; same-world calls
        # keep returning the identical Mesh object for jit cache hits.
        key = tuple((d.process_index, d.id) for d in devs)
        mesh = _flat_mesh_cache.get(key)
        if mesh is None:
            _flat_mesh_cache.clear()
            mesh = Mesh(np.asarray(devs), (spmd.DEFAULT_AXIS,))
            _flat_mesh_cache[key] = mesh
        return mesh
    return basics._get_engine().collectives_for(0).mesh


@metrics.span(scopes.SHARD_BATCH)
def shard_batch(batch):
    """Device-put a pytree so leaf dim 0 is sharded across the world.

    In-process mode the argument is the full batch; in multihost mode
    each process passes ITS shard of the global batch (reference
    semantics: every rank loads its own data) and the pieces assemble
    into one global array.
    """
    mesh = _world_mesh()
    sharding = NamedSharding(mesh, P(spmd.DEFAULT_AXIS))
    if _multihost():
        nproc = jax.process_count()

        def put(x):
            x = np.asarray(x)
            global_shape = (x.shape[0] * nproc,) + x.shape[1:]
            return jax.make_array_from_process_local_data(
                sharding, x, global_shape)

        return jax.tree.map(put, batch)
    return jax.tree.map(
        lambda x: jax.device_put(host_or_device(x), sharding), batch)


def replicate(tree):
    """Device-put a pytree fully replicated across the world (every
    process must pass the same values in multihost mode)."""
    mesh = _world_mesh()
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.device_put(host_or_device(x), sharding), tree)


def fetch(tree):
    """Host values of a replicated pytree (works on global arrays whose
    shards span processes: reads this process's replica)."""
    def get(x):
        if hasattr(x, "addressable_shards"):
            return np.asarray(jax.device_get(x.addressable_shards[0].data))
        return np.asarray(x)

    return jax.tree.map(get, tree)


def make_data_parallel_step(loss_fn: Callable,
                            optimizer: optax.GradientTransformation,
                            compression=Compression.none,
                            op: str = AVERAGE,
                            backward_passes_per_step: int = 1,
                            donate: bool = True):
    """Build a jitted SPMD train step: (params, opt_state, batch) ->
    (params, opt_state, loss).

    ``loss_fn(params, batch) -> scalar`` is written per-shard; gradients
    are world-averaged by the wrapped optimizer before the update.
    """
    mesh = _world_mesh()
    axis = spmd.DEFAULT_AXIS
    dist_opt = DistributedOptimizer(
        optimizer, compression=compression, op=op,
        backward_passes_per_step=backward_passes_per_step, axis_name=axis)

    def shard_step(params, opt_state, batch):
        # Traced under hvd.model: jvp(hvd.model) forward,
        # transpose(jvp(hvd.model)) backward (common/scopes.py).
        loss, grads = jax.value_and_grad(
            jax.named_scope(scopes.MODEL)(loss_fn))(params, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = dist_opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        # Replicated outputs: loss averaged across shards.
        loss = jax.lax.pmean(loss, axis)
        return params, opt_state, loss

    mapped = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P(), P(axis)),
        out_specs=(P(), P(), P()),
        check_vma=False)
    donate_args = (0, 1) if donate else ()
    jitted = jax.jit(mapped, donate_argnums=donate_args)

    @metrics.span(scopes.OPTIMIZER_INIT)
    def init(params):
        return dist_opt.init(params)

    return jitted, init


def make_sharded_jit_step(loss_fn: Callable,
                          optimizer: optax.GradientTransformation,
                          donate: bool = True):
    """Compiler-driven DP: jit with replicated params + dim0-sharded batch;
    XLA inserts the gradient all-reduce (mean over the batch axis)."""
    mesh = _world_mesh()
    rep = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(spmd.DEFAULT_AXIS))

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            jax.named_scope(scopes.MODEL)(loss_fn))(params, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    jitted = jax.jit(
        step,
        in_shardings=(rep, rep, sharded),
        out_shardings=(rep, rep, rep),
        donate_argnums=(0, 1) if donate else ())

    return jitted, optimizer.init


def metric_average(value, name: Optional[str] = None):
    """Average a host-side metric across ranks (reference: the
    ``metric_average`` helper in examples/pytorch/pytorch_mnist.py)."""
    from ..ops import api as eager
    size = basics.size()
    stacked = np.tile(np.asarray(value, dtype=np.float32).reshape(-1),
                      (size, 1))
    return float(np.asarray(eager.allreduce(
        stacked, op=AVERAGE, name=name or "metric")).reshape(-1)[0])
