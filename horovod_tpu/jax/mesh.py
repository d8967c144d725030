"""Mesh construction for pods and multislice.

Reference parity: the role of `MPIContext`'s communicator layout
(world/local/cross comms, `horovod/common/mpi/mpi_context.cc`) — on
TPU the "communicator" is the device mesh, and how devices map onto
its axes decides whether a collective rides ICI (fast, within a
slice) or DCN (across slices/hosts).

* ``create_mesh`` — single-slice: wraps
  ``jax.experimental.mesh_utils.create_device_mesh`` so axes follow
  the physical torus (XLA's collectives then use nearest-neighbor ICI
  rings).
* ``create_hybrid_mesh`` — multislice/multi-host: outer axes span DCN
  (data parallel across slices — the reference's "cross" dimension),
  inner axes span ICI within a slice ("local" dimension).  Mirrors
  the reference's hierarchical split: cheap collectives inside, one
  aggregated hop across.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..common import metrics, scopes

__all__ = ["create_mesh", "create_hybrid_mesh"]


@metrics.span(scopes.MESH)
def create_mesh(axis_shapes: Sequence[int],
                axis_names: Sequence[str],
                devices: Optional[Sequence] = None) -> Mesh:
    """Physical-topology-aware mesh over one slice.

    ``create_mesh((4, 2), ("dp", "tp"))`` on 8 chips lays ``tp`` along
    contiguous ICI neighbors.  Falls back to a simple reshape when the
    platform exposes no topology (CPU test worlds).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod(axis_shapes))
    if n != len(devices):
        raise ValueError("mesh shape %r needs %d devices, have %d"
                         % (tuple(axis_shapes), n, len(devices)))
    try:
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_device_mesh(
            tuple(axis_shapes), devices=devices)
    except Exception:
        if devices[0].platform == "tpu":
            raise  # real topology IS available: the config is wrong
        # cpu/test world without topology info: plain reshape
        arr = np.asarray(devices).reshape(tuple(axis_shapes))
    return Mesh(arr, tuple(axis_names))


def create_hybrid_mesh(ici_axis_shapes: Sequence[int],
                       dcn_axis_shapes: Sequence[int],
                       axis_names: Sequence[str],
                       devices: Optional[Sequence] = None) -> Mesh:
    """Multislice mesh: ``dcn_axis_shapes`` (outer, slow network) ×
    ``ici_axis_shapes`` (inner, fast interconnect).

    ``create_hybrid_mesh((1, 8), (2, 1), ("dp", "mp"))`` over 2 slices
    of 8 chips: ``dp`` crosses slices on DCN, ``mp`` stays on ICI —
    shard model axes on ICI, replicate/batch across DCN (the
    reference's hierarchical-allreduce layout as a mesh).

    Axis ``i``'s global size is ``dcn[i] * ici[i]``; names apply to
    the combined axes.  Falls back to a reshape when slice topology is
    unavailable (CPU test worlds), preserving the outer/inner order.
    """
    if len(ici_axis_shapes) != len(dcn_axis_shapes) or \
            len(ici_axis_shapes) != len(axis_names):
        raise ValueError("ici/dcn shapes and names must align per axis")
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod(ici_axis_shapes)) * int(np.prod(dcn_axis_shapes))
    if n != len(devices):
        raise ValueError("hybrid mesh needs %d devices, have %d"
                         % (n, len(devices)))
    try:
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_axis_shapes), tuple(dcn_axis_shapes),
            devices=devices)
    except Exception:
        if devices[0].platform == "tpu":
            raise  # slice topology IS available: the shapes are wrong
        # cpu/test world without slice metadata: outer-major reshape,
        # then merge each (dcn, ici) axis pair
        outer = np.asarray(devices).reshape(
            tuple(dcn_axis_shapes) + tuple(ici_axis_shapes))
        k = len(ici_axis_shapes)
        perm = [v for i in range(k) for v in (i, k + i)]
        arr = outer.transpose(perm).reshape(
            tuple(d * i for d, i in zip(dcn_axis_shapes,
                                        ici_axis_shapes)))
    return Mesh(arr, tuple(axis_names))
