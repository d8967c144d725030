"""What the one-process job and every rank of the multi-process job share:
the device check, JAX's own compile events, the chunked window, the traced
tail and its reduction.

A chunk is ``chunk_steps`` steps ended by one ``block_until_ready``; its
wall time over its steps is one reading of the step time.  The window is
chunks back to back; the traced tail is a few more chunks under the
profiler, after the window, so that the window's numbers never pay for
the tracing.
"""

import collections
import math
import os
import shutil
import time

TRACE_SPAN = "yardstick.traced"


class Refused(Exception):
    """The run may not produce a result here: wrong platform, too few
    chips."""


def configure_jax():
    """Before anything compiles: every program goes to the persistent
    cache, however quick its compilation, so that only a cell's first run
    in a checkout compiles.  Where the cache lives is the program's
    decision (``common/device.py: place_compile_cache``)."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """JAX's own monitoring events in this process: requests to the
    persistent compile cache and its hits (``chip_smoke.CacheCounter``),
    and every executable built or loaded (one event each, hit or miss)."""

    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"
    COMPILES = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **_: self.counts.update([event]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_kw: self.counts.update([event]))

    def snapshot(self):
        return {"requests": self.counts[self.REQUESTS],
                "hits": self.counts[self.HITS],
                "compiles": self.counts[self.COMPILES]}


def check_devices(chips, local_chips, rehearsal):
    """The devices of this process, or ``Refused``.  A rehearsal runs on
    the CPU and says so; a measurement runs on the TPU and nowhere else."""
    import jax
    devices = jax.devices()
    want = "cpu" if rehearsal else "tpu"
    if devices[0].platform != want:
        raise Refused("JAX found platform %r, not %r"
                      % (devices[0].platform, want))
    if len(devices) < chips or len(jax.local_devices()) < local_chips:
        raise Refused("the cell needs %d chips (%d in this process), JAX "
                      "finds %d (%d)" % (chips, local_chips, len(devices),
                                         len(jax.local_devices())))
    return devices


def device_record(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(devices):
    """The most each device has had to hold: the high-water mark of its
    buffers plus that of what loaded programs reserve for their
    temporaries.  ``peak_bytes_in_use`` alone leaves the second out and
    reads 0.3 GiB for a ResNet-50 step that needs 4.5 (my chip run, PR 22).
    CPU devices report nothing."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return out


def run_chunk(do_step, chunk_steps, block):
    """One chunk: ``chunk_steps`` calls of ``do_step()``, then ``block()``;
    returns its seconds."""
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("yardstick.dispatch"):
        for _ in range(chunk_steps):
            do_step()
    with jax.profiler.TraceAnnotation("yardstick.block"):
        block()
    return time.perf_counter() - t0


def run_window(chunk, seconds=None, n_chunks=None):
    """Chunks back to back for ``seconds`` (the chunk that crosses the
    limit is the last) or exactly ``n_chunks``.  ``chunk()`` returns
    (seconds, steps, loss).  Returns the chunks and the window's length."""
    chunks = []
    t0 = time.perf_counter()
    while (len(chunks) < n_chunks if n_chunks is not None
           else time.perf_counter() - t0 < seconds):
        chunks.append(chunk())
    return chunks, time.perf_counter() - t0


def traced_tail(chunk, n_chunks, trace_dir):
    """``n_chunks`` more chunks under the profiler, inside one host span
    named ``TRACE_SPAN``; returns the chunks.  The Python tracer is off:
    the spans wanted are the ``TraceAnnotation``s."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(TRACE_SPAN):
            chunks = [chunk() for _ in range(n_chunks)]
    finally:
        jax.profiler.stop_trace()
    return chunks


def reduce_trace(trace_dir, chips, keep_json=None):
    """The traced tail as plain numbers: ``trace.reduce_chip`` of the
    first of ``chips`` with its idle gaps already named by host span, and
    ``busy_s_by_chip`` for all of them."""
    from yardstick import trace as tr
    if keep_json:
        # For looking at by hand: every statistic, gzipped.
        import gzip
        import json
        data = tr.read_xplane(tr.find_xplane(trace_dir), keep_stats=True)
        os.makedirs(os.path.dirname(os.path.abspath(keep_json)),
                    exist_ok=True)
        with gzip.open(keep_json, "wt") as f:
            json.dump(data, f)
    else:
        data = tr.read_xplane(tr.find_xplane(trace_dir))
    found = tr.chips(data)
    if not set(chips) <= set(found):
        # A process bound to one chip of several still calls it by its
        # place on the host: take the only one the trace holds.
        if len(found) != 1 or len(chips) != 1:
            raise tr.TraceError("chips %s not among the traced %s"
                                % (chips, found))
        chips = found
    window = tr.span_window(data, TRACE_SPAN)
    red = tr.reduce_chip(data, chips[0], window)
    spans = [s for s in tr.host_spans(data) if s[0] != TRACE_SPAN]
    red["idle_by_span"] = tr.name_gaps(red.pop("idle_gaps"), spans)
    red["busy_s_by_chip"] = [red["busy_s"]] + [
        tr.reduce_chip(data, c, window)["busy_s"] for c in chips[1:]]
    red["describe"] = tr.describe(data)
    return red


def loss_fell(first, later):
    """Training made progress: the lowest loss read after step 0 (the
    warm-up's and the window's chunks) is below step 0's.  The loss
    straight after the warm-up alone is not a safe reading: on a batch of
    8 two-way decisions AdamW's first steps from random weights throw the
    loss about (up to 2.7 after one step, rising on 8-16 of the first 30),
    and on 3 of 28 seeds the 11th step read above step 0 though every run
    read to its end got far below it (my chip runs, PR 22).  An optimizer
    that does nothing or diverges never gets below step 0 at all."""
    return min(later) < first


def failed_steps(chunks):
    """Steps whose loss was not finite, counted by chunk: a chunk's loss
    is its last step's."""
    return sum(steps for _, steps, loss in chunks if not math.isfinite(loss))
