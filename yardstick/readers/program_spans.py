"""The program's own account of set-up: its host spans
(``horovod_tpu.common.metrics.span_records()``: ``hvd.init`` and its
children, ``hvd.mesh``, ``hvd.build_state``, ``hvd.broadcast``,
``hvd.shard_batch``, ``hvd.import``, and JAX's compile stages kept as
``hvd.compile_trace`` / ``_lower`` / ``_backend`` / ``_cache_read``), read in
the job's own process after the run.

Of the records that END inside set-up (process start to the first measured
step, the interval ``setup_s`` is the length of) it takes those named by
``params["spans"]`` (no such key: every record) and returns the seconds
their intervals cover together.  A union, not a sum: the stage events of
nested ``jit``s lie inside one another, and a span's children inside it.
With ``params["count"]`` it returns how many there are instead.

Nothing to read, so ``None``: a world's parent (``t_launch``: the spans are
its workers'), a program that has no host spans, and a set-up in which the
program kept none.
"""

from yardstick import trace as tr


def in_setup(records, ev, names=None):
    """``(start, end)`` of the records ``(id, parent, name, start, end,
    attributes)`` that end inside set-up and carry one of ``names``."""
    return [(start, end) for _id, _parent, name, start, end, _attrs in records
            if ev["t_start"] <= end <= ev["t_window"]
            and (names is None or name in names)]


def read(ev, params):
    if "t_launch" in ev:
        return None
    try:
        from horovod_tpu.common.metrics import span_records
    except ImportError:
        return None
    records = span_records()
    if not in_setup(records, ev):
        return None
    kept = in_setup(records, ev, params.get("spans"))
    if params.get("count"):
        return len(kept)
    return tr.total(tr.union(kept))
