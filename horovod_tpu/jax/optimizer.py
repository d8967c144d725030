"""DistributedOptimizer / DistributedGradientTape equivalents for JAX.

Reference parity (``horovod/torch/optimizer.py`` ``_DistributedOptimizer``,
``horovod/tensorflow/__init__.py`` ``DistributedOptimizer`` /
``DistributedGradientTape``): wrap the local optimizer so gradients are
averaged across the data-parallel world before the update, with optional
fp16/bf16 wire compression, gradient predivision, local aggregation
(``backward_passes_per_step``), and process-set scoping.

JAX re-design: the optimizer is an ``optax.GradientTransformation``; the
distributed wrapper is *another* GradientTransformation that allreduces
gradients first — composable, functional, jit-friendly.  Inside a
mesh-sharded step the reduce is one ``lax.psum`` a gradient, packed into
nothing: how many share one all-reduce, and where in the step it goes, is
the compiler's (the benchmark's ``collective_ms_per_step`` is the time no
compute hid: on the v5e all of it, see ``spmd.py``).  In the
multi-process world it routes through the eager engine instead.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax

from ..common import scopes
from ..common.process_sets import ProcessSet
from ..ops.xla_ops import ADASUM, AVERAGE, SUM
from . import spmd
from .compression import Compression


class _AggState(NamedTuple):
    inner: Any
    accum: Any
    counter: jnp.ndarray


AxisSpec = Optional[Union[str, Tuple[str, str]]]


def allreduce_gradients(grads, op: str = AVERAGE,
                        axis_name: AxisSpec = spmd.DEFAULT_AXIS,
                        compression=Compression.none,
                        process_set: Optional[ProcessSet] = None):
    """Average a gradient pytree across the world.

    ``axis_name`` set (inside shard_map/pjit): in-program psum, leaf by
    leaf; a ``(inner, outer)`` PAIR of axis names selects the
    hierarchical reduce over a hybrid mesh (all leaves packed into one
    buffer: reduce-scatter on ICI, cross-slice allreduce of the shards
    on DCN, all-gather back — the reference's
    ``HOROVOD_HIERARCHICAL_ALLREDUCE``).
    ``axis_name=None`` (eager, multi-process tcp world): engine allreduce
    per leaf, fused by the background cycle.

    The in-program forms are traced under ``hvd.exchange``
    (``common/scopes.py``): the collectives and what is done to the
    gradients round them (codec casts, the ``Average`` division; the
    hierarchical form's packing).
    """
    from .compression import check_reduce_safe
    check_reduce_safe(compression, "allreduce_gradients")
    if axis_name is not None:
        with jax.named_scope(scopes.EXCHANGE):
            if isinstance(axis_name, (tuple, list)):
                if compression is not Compression.none:
                    raise ValueError(
                        "compression is not supported on the hierarchical "
                        "reduce path")
                inner, outer = axis_name
                return spmd.hierarchical_allreduce_pytree(
                    grads, op=op, inner_axis=inner, outer_axis=outer)
            return spmd.allreduce_pytree(grads, op=op, axis_name=axis_name,
                                         compression=compression)
    from ..ops import api as eager
    leaves, treedef = jax.tree.flatten(grads)
    handles = []
    for i, g in enumerate(leaves):
        wire, ctx = compression.compress(g)
        handles.append((eager.allreduce_async(
            wire, op=op, name="DistributedOptimizer.gradient/%d" % i,
            process_set=process_set), ctx))
    outs = [compression.decompress(h.wait(), ctx) for h, ctx in handles]
    return jax.tree.unflatten(treedef, outs)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         named_parameters=None,
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         op: str = AVERAGE,
                         gradient_predivide_factor: float = 1.0,
                         axis_name: AxisSpec = spmd.DEFAULT_AXIS,
                         process_set: Optional[ProcessSet] = None
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer with cross-replica gradient reduction.

    Mirrors the reference constructor surface: ``compression``,
    ``backward_passes_per_step`` (local aggregation: gradients accumulate
    locally N steps, reduce once), ``op`` (Average/Sum/Adasum),
    ``gradient_predivide_factor`` (pre/post scaling split).
    ``named_parameters`` is accepted for API compatibility and unused (JAX
    pytrees are already named).
    """
    if gradient_predivide_factor != 1.0 and op != AVERAGE:
        raise ValueError(
            "gradient_predivide_factor only applies to Average, as in the "
            "reference")
    if op == ADASUM and axis_name is not None:
        raise ValueError(
            "Adasum runs through the eager engine (axis_name=None)")
    n_agg = int(backward_passes_per_step)
    if n_agg < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    pre = 1.0 / gradient_predivide_factor
    post = gradient_predivide_factor

    def reduce_now(grads):
        if op == AVERAGE and gradient_predivide_factor != 1.0:
            scaled = jax.tree.map(
                lambda g: g * jnp.asarray(pre, g.dtype), grads)
            red = allreduce_gradients(scaled, op=SUM, axis_name=axis_name,
                                      compression=compression,
                                      process_set=process_set)
            if isinstance(axis_name, (tuple, list)):
                denom = (spmd.size(axis_name[0])
                         * spmd.size(axis_name[1]))
            elif axis_name is not None:
                denom = spmd.size(axis_name)
            else:
                denom = (process_set.size() if process_set
                         else _world())
            return jax.tree.map(
                lambda g: g * jnp.asarray(post / denom, g.dtype), red)
        return allreduce_gradients(grads, op=op, axis_name=axis_name,
                                   compression=compression,
                                   process_set=process_set)

    def _world():
        from ..common import basics
        return basics.size()

    def init_fn(params):
        inner = optimizer.init(params)
        if n_agg == 1:
            return _AggState(inner, None, jnp.zeros((), jnp.int32))
        accum = jax.tree.map(jnp.zeros_like, params)
        return _AggState(inner, accum, jnp.zeros((), jnp.int32))

    def update_fn(grads, state: _AggState, params=None, **extra):
        if n_agg == 1:
            reduced = reduce_now(grads)
            updates, inner = optimizer.update(reduced, state.inner, params,
                                              **extra)
            return updates, _AggState(inner, None, state.counter + 1)
        # Local aggregation (backward_passes_per_step > 1): accumulate
        # locally, reduce+apply every n_agg-th call, no-op updates between.
        accum = jax.tree.map(lambda a, g: a + g, state.accum, grads)
        counter = state.counter + 1
        do_step = counter % n_agg == 0

        def apply_branch(operand):
            acc, inner = operand
            avg = jax.tree.map(lambda a: a / n_agg, acc)
            reduced = reduce_now(avg)
            updates, inner2 = optimizer.update(reduced, inner, params,
                                               **extra)
            return updates, jax.tree.map(jnp.zeros_like, acc), inner2

        def skip_branch(operand):
            acc, inner = operand
            zeros = jax.tree.map(jnp.zeros_like, acc)
            return zeros, acc, inner

        if axis_name is None:
            # Eager world: python control flow is fine.
            if int(counter) % n_agg == 0:
                updates, accum, inner = apply_branch((accum, state.inner))
            else:
                updates, accum, inner = skip_branch((accum, state.inner))
        else:
            updates, accum, inner = jax.lax.cond(
                do_step, apply_branch, skip_branch, (accum, state.inner))
        return updates, _AggState(inner, accum, counter)

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


class DistributedGradientTape:
    """Reference ``hvd.DistributedGradientTape`` analog for JAX.

    Wraps a scalar loss function; ``gradient(params, *args)`` returns
    world-averaged gradients.  Use inside a mesh-sharded jitted step::

        tape = hvd.DistributedGradientTape(loss_fn)
        loss, grads = tape.gradient(params, batch)
    """

    def __init__(self, loss_fn, compression=Compression.none,
                 op: str = AVERAGE,
                 axis_name: AxisSpec = spmd.DEFAULT_AXIS,
                 process_set: Optional[ProcessSet] = None):
        self._grad_fn = jax.value_and_grad(loss_fn)
        self.compression = compression
        self.op = op
        self.axis_name = axis_name
        self.process_set = process_set

    def gradient(self, params, *args, **kwargs):
        loss, grads = self._grad_fn(params, *args, **kwargs)
        grads = allreduce_gradients(
            grads, op=self.op, axis_name=self.axis_name,
            compression=self.compression, process_set=self.process_set)
        return loss, grads
