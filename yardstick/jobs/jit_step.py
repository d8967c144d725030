"""One process, one jitted training step: the cells whose work is inside
``jax.jit`` (``hvd.make_data_parallel_step``, a model's ``make_*_step``).

The builder named by the configuration hands over the framework's step
with its state and batch; this file times it.  Steps of a chunk are
dispatched back to back with no blocking, as a user's loop does, and the
chunk ends with one ``block_until_ready`` on the loss and on one leaf of
the new parameters.
"""

import math
import os
import time

from yardstick import measure


def run(ctx):
    """The evidence of one run (see ``report.py`` for what reads it)."""
    import jax
    measure.configure_jax()
    counter = measure.CompileCounter()
    import horovod_tpu.jax as hvd

    cell, spec = ctx["cell"], ctx["cell"]["spec"]
    chips = cell["chips"]
    devices = measure.check_devices(chips, chips, ctx["rehearsal"])
    # With no launcher env, init() takes every device it is not told to
    # leave alone; it also places the compile cache.
    hvd.init(devices=None if chips == len(devices) else devices[:chips])
    t_init = time.time()
    used = devices[:chips]
    builder = ctx["manifest"].module("builders", cell["builder"])
    job = builder.jit_step(cell, ctx["seed"], hvd, used)
    step, batch, probe = job["step"], job["batch"], job["probe"]
    state = job["state"]

    # Outside the window: the plain reference on the weights the first
    # step will see (the step donates them).
    loss_ref = job["reference"](state)
    after_reference = measure.peak_bytes(used)

    t0 = time.perf_counter()
    state, loss = step(state, batch)
    loss_first = float(jax.block_until_ready(loss))
    first_step_s = time.perf_counter() - t0

    chunk_steps = spec["chunk_steps"]

    def one_step():
        nonlocal state, loss
        state, loss = step(state, batch)

    def chunk():
        secs = measure.run_chunk(
            one_step, chunk_steps,
            lambda: jax.block_until_ready((loss, probe(state))))
        return secs, chunk_steps, float(loss)

    warm = [chunk() for _ in range(spec.get("warmup_chunks", 1))]
    loss_warm = warm[-1][2]
    setup_cache = counter.snapshot()

    t_window = time.time()
    chunks, window_s = measure.run_window(chunk, seconds=ctx["seconds"])
    in_window = counter.snapshot()["compiles"] - setup_cache["compiles"]
    peaks = measure.peak_bytes(used)

    traced = None
    if ctx["trace"]:
        trace_dir = os.path.join(ctx["out_dir"], "trace")
        tail = measure.traced_tail(chunk, spec.get("trace_chunks", 2),
                                   trace_dir)
        traced = {"chunks": [c[:2] for c in tail],
                  "steps": sum(c[1] for c in tail)}
        if not ctx["rehearsal"]:
            traced["reduction"] = measure.reduce_trace(
                trace_dir, [d.id for d in used], ctx.get("keep_trace_json"))
    hvd.shutdown()

    tol = job["loss_rtol"]
    later = [c[2] for c in warm + chunks]
    checks = {
        "losses finite": all(math.isfinite(x) for x in [loss_first] + later),
        "lowest loss after step 0 %.6g below step 0's %.6g"
        % (min(later), loss_first): measure.loss_fell(loss_first, later),
        "step-0 loss %.6g within %g of the plain reference %.6g"
        % (loss_first, tol, loss_ref):
            abs(loss_first - loss_ref) <= tol * abs(loss_ref),
        "no compile in the window (%d)" % in_window: in_window == 0,
    }
    return {
        "device": measure.device_record(devices),
        "chips": chips,
        "samples_per_step": job["samples_per_step"],
        "flops_per_sample": job["flops_per_sample"],
        "grad_bytes": job["grad_bytes"],
        "kernels": job["kernels"],
        "t_start": ctx["t_start"], "t_init": [t_init], "t_window": t_window,
        "first_step_s": first_step_s,
        "cache": setup_cache,
        "compiles_in_window": in_window,
        "chunks": [c[:2] for c in chunks],
        "window_s": window_s,
        "steps": sum(c[1] for c in chunks),
        "failed_steps": measure.failed_steps(chunks),
        "losses": {"reference": loss_ref, "first": loss_first,
                   "warm": loss_warm, "last": chunks[-1][2]},
        "checks": checks,
        "peak_bytes": peaks,
        "peak_bytes_after_reference": after_reference,
        "counters": {},
        "traced": traced,
    }
