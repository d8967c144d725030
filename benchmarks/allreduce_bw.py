"""Allreduce bus-bandwidth harness — the BASELINE.md north-star metric.

Reference parity: the role of NCCL's ``all_reduce_perf`` /
``docs/benchmarks.rst`` bus-bandwidth accounting.  For an allreduce of
``S`` bytes over ``n`` devices, the data each device must move is
``2*(n-1)/n * S`` ("bus bytes", the NCCL convention), so

    bus_bw = 2*(n-1)/n * S / t_per_allreduce.

Sweeps message sizes, reports per-size bus GB/s and, when the
per-device link speed is known (``--link-gbps``, e.g. ICI), the
efficiency fraction.  Runs on whatever world is available:

* real TPU chips: ``python benchmarks/allreduce_bw.py``
* 8-device CPU world:
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8
  JAX_PLATFORMS=cpu python benchmarks/allreduce_bw.py``

Prints one JSON line per size plus a summary line.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# A differential host-clock window is not trusted to resolve a SINGLE op
# faster than ~20 us; small messages amortize by batching ops per
# measurement window until the differential window itself is far above
# that floor, so small-message dispatch cost becomes a real tracked
# number instead of "below timer resolution".
_RES_S = 20e-6
_TARGET_WINDOW_S = 5e-3
_MAX_AMORTIZE = 512


def measure_per_op(timed, iters):
    """(per_op_seconds, ops_per_window, resolvable) via differential
    (2N − N) windows; ``timed(total_ops)`` runs that many ops before
    one fetch barrier.  When a probe shows the per-op time below the
    timer resolution, the op count per window scales up (capped) so
    the differential window is well above it."""
    t1 = timed(iters)
    t2 = timed(2 * iters)
    if t2 <= t1:
        # The shorter window took longer: it still paid warm-up.  That
        # is noise, not a fast op; read as one it would amortize up to
        # 512x (and tens of 8-device collective runs in flight
        # deadlock XLA:CPU's rendezvous).  Probe once more.
        t1 = timed(iters)
        t2 = timed(2 * iters)
    diff = max(t2 - t1, 1e-12)
    per_op = diff / iters
    inner = 1
    if per_op < _RES_S:
        est = max(per_op, 1e-9)
        inner = min(_MAX_AMORTIZE,
                    max(2, int(np.ceil(_TARGET_WINDOW_S
                                       / (est * iters)))))
        t1 = timed(iters * inner)
        t2 = timed(2 * iters * inner)
        diff = max(t2 - t1, 1e-12)
        per_op = diff / (iters * inner)
    resolvable = per_op >= _RES_S or diff >= 1e-3
    return per_op, iters * inner, resolvable


def bus_bytes(op, n, payload_bytes):
    """NCCL all_*_perf bus-bytes conventions per op: the wire traffic a
    perfect algorithm moves per device, so bus GB/s is comparable
    across ops and world sizes.  ``payload_bytes`` is THIS rank's
    payload (the allgather convention scales it to the gathered total
    internally)."""
    s = float(payload_bytes)
    if op == "allreduce":
        return 2.0 * (n - 1) / n * s
    if op == "allgather":
        return (n - 1) / n * (n * s)   # total gathered buffer
    if op in ("reducescatter", "alltoall"):
        return (n - 1) / n * s
    if op == "broadcast":
        return s * (n - 1) / n
    raise ValueError("unknown op %r" % op)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16,64,256",
                    help="comma list of message sizes in MiB")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--link-gbps", type=float, default=None,
                    help="per-device injection bandwidth in GB/s "
                         "(e.g. ICI) for efficiency accounting")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--cpu-devices", type=int, default=None,
                    help="force an N-device virtual CPU world (the "
                         "test topology; overrides any TPU plugin)")
    ap.add_argument("--eager", action="store_true",
                    help="measure the hvd eager API path (hvd.allreduce"
                         " of a device array) instead of the raw jit "
                         "path; under the launcher's --multihost mode "
                         "this exercises negotiation + the device-"
                         "resident executor")
    ap.add_argument("--eager-async", action="store_true",
                    help="eager path, but issue every iteration's op "
                         "with allreduce_async and wait at the end — "
                         "the DistributedOptimizer traffic shape, and "
                         "the apples-to-apples comparison against the "
                         "jit loop (which also dispatches all iters "
                         "before its single fetch barrier)")
    ap.add_argument("--burst", type=int, default=None,
                    help="with --eager-async: enqueue BURST ops per "
                         "wait round (a fixed-size gradient bucket, "
                         "like one optimizer step) instead of all "
                         "iters at once — keeps the fused group "
                         "composition identical between timing passes")
    ap.add_argument("--op", default="allreduce",
                    choices=["allreduce", "allgather", "alltoall",
                             "reducescatter", "broadcast"],
                    help="which eager collective to measure "
                         "(non-allreduce ops need --eager; exercised "
                         "by podcheck's hier A/B so the multi-chip "
                         "legs of every op are pod-measured)")
    ap.add_argument("--compression", default="none",
                    choices=["none", "fp16", "bf16", "int8", "fp8"],
                    help="cross-host wire codec A/B (exports "
                         "HOROVOD_CROSS_HOST_COMPRESSION before init; "
                         "engages on the hier leg above the "
                         "hierarchical threshold).  Bus-bytes math "
                         "uses the WIRE itemsize so reported GB/s "
                         "stays NCCL-convention-comparable across "
                         "codecs")
    ap.add_argument("--fast-path", default=None, choices=["on", "off"],
                    help="steady-state fast path A/B (exports "
                         "HOROVOD_FAST_PATH before init): after "
                         "HOROVOD_FAST_PATH_WARM_CYCLES identical "
                         "cycles the engine freezes the negotiated "
                         "schedule and dispatches straight off it.  "
                         "Each size reports negotiation cycles vs "
                         "frozen (negotiation-skipped) cycles and the "
                         "steady-state cycle time from the live "
                         "metrics; the run self-attributes with a "
                         "levers.fastpath JSON line")
    ap.add_argument("--fault", default=None, metavar="SITE:SPEC",
                    help="resilience A/B: arm HVD_TPU_FAULT with this "
                         "spec before init (e.g. "
                         "'mh.leg.drop:drop@times=2' for retry-under-"
                         "flake GB/s, an unbounded drop for degraded "
                         "hier->flat GB/s) and self-attribute the run "
                         "with a levers.resilience JSON line (retries "
                         "absorbed, routes demoted, failure ledger) so "
                         "the A/B delta is attributable to the fault, "
                         "not trusted from the printed math")
    args = ap.parse_args()
    if args.op != "allreduce" and not args.eager:
        ap.error("--op %s requires --eager (the jit path and the async "
                 "burst only time allreduce)" % args.op)
    if args.compression != "none" and not (args.eager
                                           or args.eager_async):
        ap.error("--compression requires --eager/--eager-async "
                 "(the codec lives on the eager multihost hier "
                 "leg; the raw jit path has no compression seam)")
    if args.fault and not (args.eager or args.eager_async):
        ap.error("--fault requires --eager/--eager-async (the "
                 "mh.leg.* / mh.deadline.* seams live on the eager "
                 "multihost data plane)")
    if args.fast_path and not (args.eager or args.eager_async):
        ap.error("--fast-path requires --eager/--eager-async (the "
                 "frozen-schedule seam lives on the negotiating "
                 "engines; the raw jit path never negotiates)")
    if args.fast_path:
        # Pre-init export, like --compression: an explicit off leg must
        # OVERRIDE ambient HOROVOD_FAST_PATH so the A/B baseline really
        # negotiates every cycle.
        import os
        os.environ["HOROVOD_FAST_PATH"] = (
            "1" if args.fast_path == "on" else "0")
    if args.fault:
        # Pre-init export, like --compression: faultline parses the
        # spec at hvd.init() and rejects malformed/misplaced actions
        # (e.g. drop at a non-skip site) loudly at parse time.
        import os
        prior = os.environ.get("HVD_TPU_FAULT")
        os.environ["HVD_TPU_FAULT"] = (
            prior + "," + args.fault if prior else args.fault)
    # Export unconditionally: --compression none must OVERRIDE a
    # pre-set HOROVOD_CROSS_HOST_COMPRESSION (a stale env from the A/B
    # recipe would otherwise silently compress the baseline leg while
    # the bus math assumed a full-precision wire).
    import os
    os.environ["HOROVOD_CROSS_HOST_COMPRESSION"] = args.compression

    if args.cpu_devices:
        import os
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=%d"
                % args.cpu_devices).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")

    if args.eager or args.eager_async:
        return run_eager(args)

    import os
    hvd = None
    if os.environ.get("HOROVOD_CONTROLLER") == "multihost":
        # Launched under the runner's --multihost mode: join the global
        # JAX runtime so the jit path sees the whole pod.
        import horovod_tpu as hvd
        hvd.init()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    multiproc = jax.process_count() > 1
    if multiproc:
        # Same topology as the eager multihost plane: one device per
        # process (device 0), so eager-vs-jit numbers are comparable.
        by_proc = {}
        for d in sorted(jax.devices(), key=lambda d: d.id):
            by_proc.setdefault(d.process_index, []).append(d)
        devs = [by_proc[p][0] for p in sorted(by_proc)]
    else:
        devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("dp",))
    dtype = jnp.dtype(args.dtype)

    @jax.jit
    def allreduce(x):
        # Every device holds a FULL size-S row (the NCCL
        # all_reduce_perf convention: per-rank buffer = message size);
        # the axis-0 sum of the row-sharded input lowers to one
        # all-reduce over the mesh.
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P())).sum(axis=0)

    results = []
    for size_mb in [float(s) for s in args.sizes_mb.split(",")]:
        size_bytes = int(size_mb * 2 ** 20)
        elems = max(1, size_bytes // dtype.itemsize)
        if multiproc:
            x = jax.make_array_from_process_local_data(
                NamedSharding(mesh, P("dp", None)),
                np.ones((1, elems), dtype), (n, elems))
        else:
            x = jax.device_put(
                jnp.ones((n, elems), dtype),
                NamedSharding(mesh, P("dp", None)))

        # Each window ends by fetching one scalar of the last result
        # to the host; the (2N - N) difference cancels that cost.
        fetch = jax.jit(lambda v: v[0].astype(jnp.float32))

        def timed(iters):
            t0 = time.perf_counter()
            y = None
            for _ in range(iters):
                y = allreduce(x)
            if y is not None:
                float(np.asarray(fetch(y)))
            return time.perf_counter() - t0

        timed(args.warmup)
        per_op, opw, resolvable = measure_per_op(timed, args.iters)
        bb = bus_bytes("allreduce", n, elems * dtype.itemsize)
        bus_gbps = bb / per_op / 1e9 if resolvable else None
        rec = {"metric": "allreduce_bus_bandwidth",
               "size_mb": size_mb, "devices": n,
               "time_us": round(per_op * 1e6, 2),
               "ops_per_window": opw,
               "bus_gb_per_sec": (round(bus_gbps, 3)
                                  if bus_gbps is not None else None)}
        if not resolvable:
            rec["note"] = ("below timer resolution even amortized "
                           "over %d ops/window" % opw)
        elif n == 1:
            # Degenerate world: bus bytes are zero, but per-op time is
            # still the dispatch + HBM-traversal cost of the compiled
            # collective — record the effective HBM rate instead.
            rec["hbm_gb_per_sec"] = round(
                elems * dtype.itemsize / per_op / 1e9, 3)
        if args.link_gbps and bus_gbps is not None:
            rec["efficiency"] = round(bus_gbps / args.link_gbps, 4)
        results.append(rec)
        if jax.process_index() == 0:
            print(json.dumps(rec))

    best = max((r["bus_gb_per_sec"] for r in results
                if r["bus_gb_per_sec"] is not None), default=0.0)
    summary = {"metric": "allreduce_bus_bandwidth_peak",
               "value": best, "unit": "GB/s", "devices": n}
    if args.link_gbps:
        summary["efficiency_vs_link"] = round(best / args.link_gbps, 4)
    if jax.process_index() == 0:
        print(json.dumps(summary))
    if hvd is not None:
        hvd.shutdown()


def run_eager(args):
    """The hvd eager-API path: negotiation + device-resident executor.

    Under ``python -m horovod_tpu.runner -np N --multihost`` each
    process contributes its own device array (per-rank semantics); in a
    single process the in-process SPMD world takes rank-major stacked
    input.  The jit path above is the floor this path is measured
    against (VERDICT r2: eager within ~2x of jit bytes/s).
    """
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    # Per-rank tensors only exist in the multi-process world; a single
    # process means the in-process SPMD engine (rank-major stacked
    # input), regardless of hvd.size().
    multihost = jax.process_count() > 1
    dtype = jnp.dtype(args.dtype)
    op = args.op
    # Codec A/B: ask the engine's OWN gate (codec resolution + hier
    # eligibility) per size, so the reported wire bytes are exactly
    # what production would put on DCN — no second copy of the gate
    # logic to drift.  The in-process world has no cross-host leg; the
    # codec stays inert there and wire == payload.
    mc = None
    if args.compression != "none" and multihost:
        from horovod_tpu.common import basics
        mc = basics._get_mh_engine().collectives_for(0)
    # The RESOLVED codec label (e.g. fp8 falls back to 'fp8-as-bf16'
    # on jax without float8): the metrics series carry this name, not
    # the requested one.
    resolved_codec = (mc._codec.name
                      if mc is not None and mc._codec is not None
                      else args.compression)

    def run_op(x, name):
        if op == "allreduce":
            return hvd.allreduce(x, op=hvd.Sum, name=name)
        if op == "allgather":
            return hvd.allgather(x, name=name)
        if op == "broadcast":
            return hvd.broadcast(x, root_rank=0, name=name)
        if op == "alltoall":
            return hvd.alltoall(x, name=name)  # uniform splits
        if op == "reducescatter":
            return hvd.reducescatter(x, op=hvd.Sum, name=name)
        raise ValueError(op)

    results = []
    for size_mb in [float(s) for s in args.sizes_mb.split(",")]:
        size_bytes = int(size_mb * 2 ** 20)
        # dim0 a multiple of the world size so uniform alltoall and
        # reducescatter chunking hold for every op uniformly.
        elems = max(n, (-(-max(1, size_bytes // dtype.itemsize) // n))
                    * n)
        if multihost:
            x = jnp.full((elems,), 1.0, dtype)   # this rank's payload
        else:
            x = jnp.ones((n, elems), dtype)      # rank-major stacked
        tag = "bw.%s.%s" % (op, size_mb)

        if args.eager_async:
            seq = [0]

            def timed(iters):
                # Burst shape: B async enqueues then one synchronize
                # (one optimizer step's gradient bucket; B = all iters
                # unless --burst caps it) — the negotiation/dispatch/
                # execution pipeline overlaps across in-flight ops the
                # way the jit loop's N dispatches overlap before its
                # single fetch barrier.  Unique in-flight names per op
                # (the engine's duplicate-name contract).
                burst = args.burst or iters
                t0 = time.perf_counter()
                y = None
                done = 0
                while done < iters:
                    hs = []
                    for _ in range(min(burst, iters - done)):
                        seq[0] += 1
                        hs.append(hvd.allreduce_async(
                            x, op=hvd.Sum,
                            name="%s.%d" % (tag, seq[0])))
                    done += len(hs)
                    for h in hs:
                        y = hvd.synchronize(h)
                if y is not None:
                    float(np.asarray(y).reshape(-1)[0])  # fetch barrier
                return time.perf_counter() - t0
        else:
            seq = [0]

            def timed(iters):
                t0 = time.perf_counter()
                y = None
                for _ in range(iters):
                    seq[0] += 1
                    y = run_op(x, "%s.%d" % (tag, seq[0]))
                if y is not None:
                    float(np.asarray(y).reshape(-1)[0])  # fetch barrier
                return time.perf_counter() - t0

        def _fp_counters():
            # Live-metrics reading of the fast path's effect: counts of
            # negotiated vs frozen (negotiation-skipped) cycles plus the
            # engine_cycle_seconds running (sum, count) — per-size
            # deltas of these are the A/B evidence, not printed math.
            from horovod_tpu.common.metrics import series_sum, snapshot
            s = c = 0.0
            fam = snapshot().get("engine_cycle_seconds") or {}
            for row in fam.get("series", ()):
                s += float(row.get("sum", 0.0))
                c += float(row.get("count", 0.0))
            return (series_sum("engine_cycles_total"),
                    series_sum("fastpath_frozen_cycles_total"), s, c)

        def _compressed_count():
            # Engagement observed from the engine's own counter, not a
            # re-derivation of its per-op gate bytes (padding /
            # size-class rounding differs per op and would drift).
            if mc is None:
                return 0.0
            from horovod_tpu.common.metrics import series_sum
            return series_sum("mh_compressed_collectives_total", op=op)

        cc_before = _compressed_count()
        fp0 = _fp_counters() if args.fast_path else None
        timed(args.warmup)
        engaged = _compressed_count() > cc_before
        per_op, opw, resolvable = measure_per_op(timed, args.iters)
        fp1 = _fp_counters() if args.fast_path else None
        payload_bytes = elems * dtype.itemsize
        # Wire bytes at the engine's accounting: the bus-bytes
        # convention uses the WIRE itemsize when the codec engaged on
        # the warmup ops, so GB/s stays NCCL-comparable across codecs
        # (the A/B measures the same logical transfer, cheaper on the
        # wire).
        wire_bytes = payload_bytes
        codec_obj = mc._wire_codec(dtype) if (mc is not None
                                              and engaged) else None
        if codec_obj is not None:
            wire_bytes = mc._wire_nbytes(codec_obj, elems)
        bb = bus_bytes(op, n, wire_bytes)
        bus_gbps = bb / per_op / 1e9 if resolvable else None
        rec = {"metric": "%s_bus_bandwidth" % op,
               "path": "eager_async" if args.eager_async else "eager",
               "mode": "multihost" if multihost else "inprocess",
               "size_mb": size_mb, "ranks": n,
               "time_us": round(per_op * 1e6, 2),
               "ops_per_window": opw,
               "bus_gb_per_sec": (round(bus_gbps, 3)
                                  if bus_gbps is not None else None)}
        if args.compression != "none":
            rec["compression"] = args.compression
            rec["compression_engaged"] = codec_obj is not None
            rec["wire_bytes"] = int(wire_bytes)
            rec["payload_bytes"] = int(payload_bytes)
        if args.fast_path:
            # This size's window from the engine's own counters: frozen
            # cycles ARE skipped negotiations (the two counters are
            # disjoint by design), and the steady-state cycle time is
            # the mean over negotiation cycles that still ran.
            d_cyc = fp1[0] - fp0[0]
            d_frozen = fp1[1] - fp0[1]
            d_sum, d_cnt = fp1[2] - fp0[2], fp1[3] - fp0[3]
            rec["fast_path"] = args.fast_path
            rec["negotiation_cycles"] = int(d_cyc)
            rec["negotiation_cycles_skipped"] = int(d_frozen)
            rec["cycle_time_us"] = (round(d_sum / d_cnt * 1e6, 2)
                                    if d_cnt else None)
        if not resolvable:
            rec["note"] = ("below timer resolution even amortized "
                           "over %d ops/window" % opw)
        if args.link_gbps and bus_gbps is not None:
            rec["efficiency"] = round(bus_gbps / args.link_gbps, 4)
        results.append(rec)
        if hvd.rank() == 0:
            print(json.dumps(rec))

    best = max((r["bus_gb_per_sec"] for r in results
                if r["bus_gb_per_sec"] is not None), default=0.0)
    if hvd.rank() == 0:
        summary = {"metric": "%s_bus_bandwidth_peak" % op,
                   "path": ("eager_async" if args.eager_async
                            else "eager"),
                   "value": best, "unit": "GB/s", "ranks": n}
        if args.compression != "none":
            summary["compression"] = args.compression
        if args.link_gbps:
            summary["efficiency_vs_link"] = round(best / args.link_gbps,
                                                  4)
        print(json.dumps(summary))
    if args.compression != "none" and hvd.rank() == 0:
        # The engine's own wire accounting for the whole run (warmup +
        # timing windows): what ACTUALLY crossed DCN, per path, plus
        # the last compression ratio — the self-attribution the e2e
        # test asserts on instead of trusting printed math.
        from horovod_tpu.common.metrics import series_sum as series

        print(json.dumps({
            "metric": "cross_host_wire",
            "codec": args.compression,
            "resolved_codec": resolved_codec,
            "wire_bytes_hier": int(series("mh_bus_bytes_total", op=op,
                                          path="hier")),
            "wire_bytes_flat": int(series("mh_bus_bytes_total", op=op,
                                          path="flat")),
            "compressed_collectives": int(series(
                "mh_compressed_collectives_total", op=op,
                codec=resolved_codec)),
            "compression_ratio": series("mh_compression_ratio", op=op,
                                        codec=resolved_codec),
        }))
    if args.fast_path and hvd.rank() == 0:
        # Self-attribution for the fast-path A/B: the engine's own
        # frozen/thaw evidence (per-plane freezer state, thaw reasons,
        # core idle rounds skipped) so a latency delta vs the off leg
        # is attributable to skipped negotiation, not printed math.
        from horovod_tpu.ops import fastpath

        print(json.dumps({
            "metric": "fastpath_levers",
            "fast_path": args.fast_path,
            "levers": {"fastpath": fastpath.describe()},
        }))
    if args.fault and hvd.rank() == 0:
        # Self-attribution for the resilience A/B: the engine's own
        # evidence of what the armed fault did to this run — retries
        # absorbed, (op, size_class) routes demoted hier->flat,
        # deadlines expired, failures by reason — so a GB/s delta vs
        # the clean leg is attributable to the injected fault.
        from horovod_tpu.common import resilience

        print(json.dumps({
            "metric": "resilience_levers",
            "fault": args.fault,
            "levers": {"resilience": resilience.describe()},
        }))
    hvd.shutdown()


if __name__ == "__main__":
    main()
