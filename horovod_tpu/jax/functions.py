"""Parameter/object broadcast + state sync helpers.

Reference parity: ``hvd.broadcast_parameters``,
``hvd.broadcast_optimizer_state``, ``hvd.broadcast_object`` (
``horovod/torch/functions.py`` and ``horovod/tensorflow/functions.py``
``broadcast_variables`` / ``broadcast_object``).
"""

from __future__ import annotations

import pickle
from typing import Any, Optional

import jax
import numpy as np

from ..common import basics, metrics, scopes
from ..common.process_sets import ProcessSet
from ..ops import api as eager
from ..ops.xla_ops import host_or_device


def _replicate(tree):
    """Place every leaf replicated over the world mesh (in-process mode)."""
    eng = basics._get_engine()
    mc = eng.collectives_for(0)
    sharding = mc._replicated_sharding
    return jax.tree.map(
        lambda x: jax.device_put(host_or_device(x), sharding), tree)


def broadcast_parameters(params, root_rank: int = 0,
                         process_set: Optional[ProcessSet] = None):
    """Make every rank hold root's parameter pytree.

    In-process SPMD world: the single controller owns one logical copy, so
    broadcast = replicate that copy across the mesh devices (an XLA
    broadcast transfer over ICI).  Multi-process world: per-leaf engine
    broadcast from ``root_rank`` — values come back per-process (device
    arrays on the eager payload plane); to feed them into the jit DP
    step afterwards, place them on the global mesh with
    ``data_parallel.replicate`` (see examples/multihost_pod_training.py).
    """
    leaves, treedef = jax.tree.flatten(params)
    with metrics.span(scopes.BROADCAST, leaves=len(leaves)):
        if basics._controller_is_spmd():
            return _replicate(params)
        handles = [eager.broadcast_async(
            g, root_rank, name="broadcast_parameters/%d" % i,
            process_set=process_set) for i, g in enumerate(leaves)]
        return jax.tree.unflatten(treedef, [h.wait() for h in handles])


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              process_set: Optional[ProcessSet] = None):
    """Broadcast optax optimizer state (reference
    ``broadcast_optimizer_state``); same mechanics as parameters since
    optax state is a pytree, and the same ``hvd.broadcast`` span."""
    return broadcast_parameters(opt_state, root_rank, process_set)


def broadcast_object(obj: Any, root_rank: int = 0,
                     name: Optional[str] = None,
                     process_set: Optional[ProcessSet] = None) -> Any:
    """Pickle-broadcast an arbitrary python object from root to all ranks
    (reference ``hvd.broadcast_object``): the payload travels as a uint8
    tensor through the same collective path as tensors do."""
    with metrics.span(scopes.BROADCAST, leaves=1):
        if basics._controller_is_spmd():
            # Single controller: root's object IS the object; round-trip
            # the bytes through a device broadcast for wire parity.
            payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
            size = basics.size()
            stacked = np.tile(payload, (size, 1))
            out = eager.broadcast(stacked, root_rank,
                                  name=name or "broadcast_object",
                                  process_set=process_set)
            return pickle.loads(np.asarray(out).tobytes())
        core = basics._get_tcp_core()
        return core.broadcast_object(obj, root_rank, name=name)


def allgather_object(obj: Any, name: Optional[str] = None,
                     process_set: Optional[ProcessSet] = None):
    """Gather one python object per rank into a list (reference
    ``hvd.allgather_object``)."""
    if basics._controller_is_spmd():
        return [obj] * basics.size()
    core = basics._get_tcp_core()
    return core.allgather_object(obj, name=name)


def _election_key(record: dict, keys) -> tuple:
    """The deterministic, order-independent comparison key shared by
    every election in the tree: evidence fields descending in ``keys``
    order, ties broken by the LOWEST rank."""
    return tuple(int(record.get(k, 0)) for k in keys) + \
        (-int(record.get("rank", 0)),)


def elect_newest(records, keys=("commit_id",)) -> dict:
    """Pure election over already-gathered records (no transport): the
    record with the greatest ``keys`` evidence tuple wins, ties to the
    lowest rank.  The serving plane's in-process replica sets use this
    with ``keys=("version",)`` — "newest model version wins" — over
    records gathered from their own threads; multi-process worlds
    gather via :func:`elect_state_root` instead."""
    return max(records, key=lambda r: _election_key(r, keys))


def elect_state_root(record: dict, name: Optional[str] = None,
                     keys=("commit_id",)):
    """Allgather one small evidence record per rank and elect the
    max-evidence rank as the sync root, identically on every rank: the
    greatest ``keys`` tuple wins, ties go to the LOWEST rank (so a
    fresh world with no evidence anywhere degenerates to the
    reference's rank-0 broadcast).  Used by ``elastic.state`` with the
    default ``keys=("commit_id",)`` — our driver does not guarantee
    survivors keep low ranks after a reshuffle, so the root must be
    elected, not assumed — and by the serving plane's weight hot-swap
    with ``keys=("version", "commit_id")``: after a replica death the
    survivors elect the NEWEST MODEL VERSION (progress as tiebreak) so
    a mid-roll failure can never resurrect stale weights.

    Returns ``(root_record, all_records)``; the election key is order-
    independent, so any transport ordering of the gathered records
    yields the same winner everywhere."""
    records = allgather_object(record, name=name or "elastic.sync.election")
    root = elect_newest(records, keys)
    return root, records
