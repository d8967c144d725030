"""In-program (SPMD) collectives: the performance path.

These are the collectives you call *inside* a jitted, mesh-sharded train
step (``jax.shard_map`` / pjit).  XLA lowers them to ICI/DCN collective HLO
inside the compiled step: the TPU equivalent of the reference's
NCCL-on-stream hot path (``ops/nccl_operations.cc``).

The pytree forms (``grouped_allreduce``, ``allreduce_pytree``) reduce leaf
by leaf and pack nothing: a collective can leave only when its operand
exists, so each gradient's all-reduce depends on that gradient alone, and
how many share one all-reduce, and where in the step it goes, is XLA's
combiner's and scheduler's decision (the TPU's default merges all of a
step's into one, without the copies a packed buffer costs).  The
benchmark's ``exchange_pack_ms_per_step`` reads what is left round the
collectives (the ``AVERAGE`` division, where it did not fuse into the
update) and ``collective_ms_per_step`` the time no compute hid.

The op surface mirrors the eager API (Sum/Average/Min/Max, prescale/
postscale, compression) so a reference user can move a call inside jit
without relearning semantics.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.xla_ops import AVERAGE, MAX, MIN, PRODUCT, SUM
from .compression import Compression, check_reduce_safe

DEFAULT_AXIS = "hvd"


def size(axis_name: str = DEFAULT_AXIS):
    """World size along the DP axis (usable inside jit)."""
    return lax.axis_size(axis_name)


def rank(axis_name: str = DEFAULT_AXIS):
    """This shard's index along the DP axis (usable inside jit)."""
    return lax.axis_index(axis_name)


def allreduce(x, op: str = AVERAGE, axis_name: str = DEFAULT_AXIS,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0,
              compression=Compression.none):
    """Cross-replica reduce inside an SPMD program."""
    check_reduce_safe(compression, "spmd.allreduce")
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, dtype=x.dtype)
    wire, ctx = compression.compress(x)
    if op in (SUM, AVERAGE):
        red = lax.psum(wire, axis_name)
        if op == AVERAGE:
            n = lax.axis_size(axis_name)
            red = (red / n).astype(wire.dtype)
    elif op == MIN:
        red = lax.pmin(wire, axis_name)
    elif op == MAX:
        red = lax.pmax(wire, axis_name)
    elif op == PRODUCT:
        red = jnp.prod(lax.all_gather(wire, axis_name), axis=0)
    else:
        raise NotImplementedError(op)
    out = compression.decompress(red, ctx)
    if postscale_factor != 1.0:
        out = out * jnp.asarray(postscale_factor, dtype=out.dtype)
    return out


def hierarchical_allreduce(x, op: str = AVERAGE,
                           inner_axis: str = "ici",
                           outer_axis: str = "dcn"):
    """The reference's ``HOROVOD_HIERARCHICAL_ALLREDUCE``
    (``ops/nccl_operations.cc``: NCCL reduce-scatter intra-node, MPI
    allreduce across, NCCL allgather back) as mesh collectives:
    ``psum_scatter`` over the fast inner axis (ICI within a slice),
    ``psum`` of the 1/inner-sized shards over the slow outer axis
    (DCN across slices), ``all_gather`` back over inner.  Only
    ``1/inner_size`` of the bytes ever cross DCN.

    Use with a ``create_hybrid_mesh`` whose DP dimension is split into
    (outer=dcn, inner=ici) axes; for Sum/Average only (like the
    reference's hierarchical path).
    """
    if op not in (SUM, AVERAGE):
        raise NotImplementedError(
            "hierarchical allreduce supports Sum/Average (reference "
            "parity: the NCCL+MPI hierarchical path was Sum-based)")
    inner = lax.axis_size(inner_axis)
    flat = jnp.ravel(x)
    pad = (-flat.shape[0]) % inner
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)])
    s = lax.psum_scatter(flat, inner_axis, scatter_dimension=0,
                         tiled=True)
    s = lax.psum(s, outer_axis)
    if op == AVERAGE:
        # Divide the 1/inner-sized shard BEFORE the gather: inner-times
        # less work, and the division cannot fuse across the collective.
        n = inner * lax.axis_size(outer_axis)
        s = (s / n).astype(flat.dtype)
    out = lax.all_gather(s, inner_axis, tiled=True)
    return out[:x.size].reshape(x.shape).astype(x.dtype)


def hierarchical_allreduce_pytree(tree, op: str = AVERAGE,
                                  inner_axis: str = "ici",
                                  outer_axis: str = "dcn"):
    """Hierarchical reduce of a pytree as one payload: one concat, one
    RS-inner/AR-outer/AG-inner round, one split.  The reduce-scatter
    needs one flat buffer padded to the inner axis, so this form (unlike
    ``grouped_allreduce``) packs, promotes mixed dtypes to their common
    one on the wire, and cannot leave before the last leaf exists."""
    leaves, treedef = jax.tree.flatten(tree)
    flats = [jnp.ravel(x) for x in leaves]
    fused = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
    red = hierarchical_allreduce(fused, op=op, inner_axis=inner_axis,
                                 outer_axis=outer_axis)
    outs, off = [], 0
    for x in leaves:
        outs.append(red[off:off + x.size].reshape(x.shape).astype(x.dtype))
        off += x.size
    return jax.tree.unflatten(treedef, outs)


def grouped_allreduce(xs: Sequence, op: str = AVERAGE,
                      axis_name: str = DEFAULT_AXIS,
                      compression=Compression.none):
    """Reduce a list of tensors, each by its own collective in its own
    dtype (see the module docstring: nothing is packed; combining
    them is XLA's)."""
    return [allreduce(x, op=op, axis_name=axis_name,
                      compression=compression) for x in xs]


def allreduce_pytree(tree, op: str = AVERAGE, axis_name: str = DEFAULT_AXIS,
                     compression=Compression.none):
    """``grouped_allreduce`` of every leaf of a pytree (gradients,
    metrics...)."""
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(
        treedef, grouped_allreduce(leaves, op=op, axis_name=axis_name,
                                   compression=compression))


def allgather(x, axis_name: str = DEFAULT_AXIS, tiled: bool = True):
    """Gather shards along dim 0 (reference allgather semantics)."""
    return lax.all_gather(x, axis_name, tiled=tiled)


def broadcast(x, root_rank: int = 0, axis_name: str = DEFAULT_AXIS):
    """Replace every shard's value with ``root_rank``'s."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def alltoall(x, axis_name: str = DEFAULT_AXIS, split_axis: int = 0,
             concat_axis: int = 0):
    """Exchange: chunk j along ``split_axis`` goes to rank j."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def reducescatter(x, op: str = SUM, axis_name: str = DEFAULT_AXIS,
                  scatter_axis: int = 0):
    """Reduce then keep this rank's dim-0 shard."""
    out = lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                           tiled=True)
    if op == AVERAGE:
        out = (out / lax.axis_size(axis_name)).astype(out.dtype)
    return out


def ppermute(x, perm, axis_name: str = DEFAULT_AXIS):
    """Neighbor exchange (``collective-permute``): the ring primitive used
    by ring attention / pipeline parallelism.  Not in the reference's op
    set — exposed because on TPU it is THE ICI-topology-native collective."""
    return lax.ppermute(x, axis_name, perm=perm)


def barrier(axis_name: str = DEFAULT_AXIS):
    """In-program barrier: a 1-element psum data dependency."""
    return lax.psum(jnp.ones((), jnp.int32), axis_name)
