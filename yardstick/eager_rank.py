"""One rank of the eager world: the loop upstream Horovod's users write,
timed.  Started by ``python -m horovod_tpu.runner -np N --multihost`` from
``jobs/eager_world.py``, one process for each chip.

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    updates, opt_state = hvd.DistributedOptimizer(opt, axis_name=None)
                             .update(grads, opt_state, params)
    params = jax.jit(optax.apply_updates)(params, updates)

``DistributedOptimizer`` sends every gradient leaf through
``allreduce_async`` (negotiate, fuse, ``ops/multihost.py``, ICI) and waits
for each handle; the optimizer it wraps has a jitted ``update``, as a user
who cares for speed gives it.  Nothing else blocks inside a chunk.

Ranks talk to the parent through files in the run's scratch directory and
to each other through nothing but the system under test; what the check of
the reduced gradients compares is exchanged through those files.  Every
rank runs the same number of chunks, fixed by rank 0 after warm-up from
its own chunk times: a rank that stopped on its own clock would leave the
others waiting in a collective.
"""

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from yardstick import measure      # noqa: E402  (no jax at import)

GRAD_SAMPLES = 16                  # entries compared of each gradient leaf
POLL_S = 0.02


def write_json(path, obj):
    """Whole or not at all: readers poll for the name."""
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def wait_json(path, limit_s):
    deadline = time.time() + limit_s
    while not os.path.exists(path):
        if time.time() > deadline:
            raise TimeoutError("no %s after %d s" % (path, limit_s))
        time.sleep(POLL_S)
    with open(path) as f:
        return json.load(f)


class StampedUpdate:
    """The wrapped optimizer's ``update``, jitted.  ``DistributedOptimizer``
    calls it with the reduced gradients, so this is where they can be
    seen: kept when asked (the check of step 0), and in the traced tail
    blocked on and stamped, which is the end of the exchange."""

    def __init__(self, optimizer):
        import jax
        self._update = jax.jit(optimizer.update)
        self.keep = False
        self.kept = None
        self.stamp = False
        self.ready_at = []

    def __call__(self, reduced, state, params=None):
        import jax
        if self.keep:
            self.kept = reduced
        if self.stamp:
            jax.block_until_ready(reduced)
            self.ready_at.append(time.perf_counter())
        return self._update(reduced, state, params)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, required=True)
    p.add_argument("--scratch", required=True)
    p.add_argument("--rehearsal", type=int, default=0)
    p.add_argument("--keep-trace-json", default=None)
    args = p.parse_args(argv)

    import jax
    import numpy as np
    import optax
    # JAX's own threshold for the persistent compile cache stays here
    # (programs that compile in under a second are not kept): the engine
    # builds small staging programs whose shapes follow the timing of a
    # run, and keeping them would make every run of a checkout a little
    # faster than the one before.
    counter = measure.CompileCounter()
    import horovod_tpu.jax as hvd
    hvd.init()                      # before any JAX computation
    t_init = time.time()
    rank, size = hvd.rank(), hvd.size()

    from yardstick import manifest as mf
    manifest = mf.load()
    cell = manifest.cell(args.workload, tiny=bool(args.rehearsal))
    spec = cell["spec"]
    devices = measure.check_devices(cell["chips"], 1, bool(args.rehearsal))
    if size != cell["chips"] or jax.process_count() != size:
        raise measure.Refused("world of %d ranks in %d processes, the cell "
                              "needs %d" % (size, jax.process_count(),
                                            cell["chips"]))
    mine = jax.local_devices()[:1]

    def out(name):
        return os.path.join(args.scratch, name)

    builder = manifest.module("builders", cell["builder"])
    job = builder.eager_parts(cell, args.seed, rank)
    batch = job["batch"]
    grad_fn = jax.jit(jax.value_and_grad(job["loss_fn"]))
    inner = StampedUpdate(job["optimizer"])
    dist = hvd.DistributedOptimizer(
        optax.GradientTransformation(job["optimizer"].init, inner),
        axis_name=None)
    apply_fn = jax.jit(optax.apply_updates, donate_argnums=0)
    # As upstream's benchmark: rank 0's weights and optimizer state to all.
    params = hvd.broadcast_parameters(job["params"], root_rank=0)
    opt_state = hvd.broadcast_optimizer_state(dist.init(params), root_rank=0)

    # Outside the window: the plain reference on this rank's batch.
    loss_ref = job["reference"](params)

    # A seeded sample of every gradient leaf, local and reduced, in one
    # jitted gather each.
    rng = np.random.default_rng(args.seed)
    picks = [rng.integers(0, x.size, GRAD_SAMPLES)
             for x in jax.tree.leaves(params)]
    sample = jax.jit(lambda tree: jax.numpy.stack(
        [leaf.reshape(-1)[idx] for leaf, idx in
         zip(jax.tree.leaves(tree), picks)]))
    checksum = jax.jit(lambda tree: sum(
        jax.numpy.sum(leaf.astype(jax.numpy.float32))
        for leaf in jax.tree.leaves(tree)))

    loss = None
    exchange_from = []

    def step():
        nonlocal params, opt_state, loss
        with jax.profiler.TraceAnnotation("yardstick.grads"):
            loss, grads = grad_fn(params, batch)
            if inner.stamp:
                jax.block_until_ready(grads)
                exchange_from.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("yardstick.exchange"):
            updates, opt_state = dist.update(grads, opt_state, params)
        with jax.profiler.TraceAnnotation("yardstick.apply"):
            params = apply_fn(params, updates)
        return grads

    # Step 0, checked: the local loss against the reference, and the
    # reduced gradients against the ranks' own, through files.
    t0 = time.perf_counter()
    inner.keep = True
    grads = step()
    loss_first = float(jax.block_until_ready(loss))
    first_step_s = time.perf_counter() - t0
    np.save(out("grads_local_%d.npy" % rank), np.asarray(sample(grads)))
    np.save(out("grads_reduced_%d.npy" % rank),
            np.asarray(sample(inner.kept)))
    inner.keep, inner.kept = False, None
    del grads
    sum_after_first = float(checksum(params))

    chunk_steps = spec["chunk_steps"]

    def chunk():
        secs = measure.run_chunk(
            step, chunk_steps,
            lambda: jax.block_until_ready(
                (loss, jax.tree.leaves(params)[0])))
        return secs, chunk_steps, float(loss)

    warm = [chunk() for _ in range(spec["warmup_chunks"])]
    loss_warm = warm[-1][2]
    setup_cache = counter.snapshot()

    # Rank 0 fixes the number of chunks from its last warm-up chunk.
    if rank == 0:
        write_json(out("plan.json"), {"n_chunks": max(
            1, math.ceil(args.seconds / warm[-1][0]))})
    n_chunks = wait_json(out("plan.json"), 120)["n_chunks"]

    counters_before = counters(hvd)
    t_window = time.time()
    chunks, window_s = measure.run_window(chunk, n_chunks=n_chunks)
    in_window = counter.snapshot()["compiles"] - setup_cache["compiles"]
    counters_after = counters(hvd)
    peaks = measure.peak_bytes(mine)
    sum_after_window = float(checksum(params))

    traced = None
    if args.trace:
        inner.stamp = True
        n_tail = spec.get("trace_chunks", 2)
        if rank == 0:
            trace_dir = out("trace")
            tail = measure.traced_tail(chunk, n_tail, trace_dir)
        else:
            tail = [chunk() for _ in range(n_tail)]
        inner.stamp = False
        traced = {"chunks": [c[:2] for c in tail],
                  "steps": sum(c[1] for c in tail),
                  "exchange_s": [b - a for a, b in
                                 zip(exchange_from, inner.ready_at)]}
        if rank == 0 and not args.rehearsal:
            traced["reduction"] = measure.reduce_trace(
                trace_dir, [mine[0].id], args.keep_trace_json)

    write_json(out("rank_%d.json" % rank), {
        "rank": rank,
        "device": measure.device_record(devices),
        "local_device": str(mine[0]),
        "samples_per_step": job["samples_per_step"],
        "flops_per_sample": job["flops_per_sample"],
        "grad_bytes": job["grad_bytes"],
        "kernels": job["kernels"],
        "loss_rtol": job["loss_rtol"],
        "t_init": t_init, "t_window": t_window,
        "first_step_s": first_step_s,
        "cache": setup_cache,
        "compiles_in_window": in_window,
        "chunks": chunks, "window_s": window_s,
        "losses": {"reference": loss_ref, "first": loss_first,
                   "warm": loss_warm, "last": chunks[-1][2]},
        "checksums": [sum_after_first, sum_after_window],
        "peak_bytes": peaks,
        "counters": {k: counters_after[k] - counters_before.get(k, 0.0)
                     for k in counters_after},
        "traced": traced,
    })
    hvd.shutdown()
    return 0


def counters(hvd):
    """The program's counters and gauges, each summed over its series."""
    return {name: sum(row.get("value", 0.0) for row in fam.get("series", ()))
            for name, fam in hvd.metrics_snapshot().items()
            if fam.get("kind") in ("counter", "gauge")}


if __name__ == "__main__":
    try:
        sys.exit(main())
    except measure.Refused as exc:
        print("yardstick rank: refused: %s" % exc, file=sys.stderr)
        sys.exit(2)
