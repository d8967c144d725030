"""The eager world: ``python -m horovod_tpu.runner -np N --multihost`` on
``yardstick/eager_rank.py``, one process for each chip, as Horovod's users
launch a job.

This parent never imports jax: it would hold the chips its ranks need.
It makes a fresh scratch directory inside the checkout, builds the native
core if the checkout has none (four ranks racing one ``make`` would
corrupt it), starts the launcher under a tag and a time limit, and reads
what the ranks wrote.  A rank that hangs is killed at the limit and the
run reports failed steps; it never outlives this function.  The check of
the reduced gradients is made here, in float64, from the ranks' files.
"""

import json
import os
import shutil
import sys
import time

import numpy as np

from yardstick import measure, procs

# A reduced float32 gradient against the float64 mean of the ranks' own:
# the sum of N float32 terms in another order differs by a few roundings
# (2^-24 each) of the largest term; anything lower than float32 on the
# wire, a dropped rank or a wrong divisor is off by 2^-9 or more.
GRAD_RTOL = 1e-5


def launch(ctx, scratch, log_path):
    """Start the world and wait for its end; returns (exit code or
    "timeout", the parent's clock at launch)."""
    cell = ctx["cell"]
    from horovod_tpu.core.client import build_library
    build_library()             # only if missing or older than its source
    argv = [sys.executable, "-m", "horovod_tpu.runner",
            "-np", str(cell["chips"]), "--multihost",
            sys.executable, os.path.join(ctx["root"], "yardstick",
                                         "eager_rank.py"),
            "--workload", cell["name"], "--seed", str(ctx["seed"]),
            "--seconds", str(ctx["seconds"]),
            "--trace", str(int(ctx["trace"])), "--scratch", scratch,
            "--rehearsal", str(int(ctx["rehearsal"]))]
    if ctx.get("keep_trace_json"):
        argv += ["--keep-trace-json", ctx["keep_trace_json"]]
    env = dict(os.environ)
    if ctx["rehearsal"]:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)      # one CPU device a rank
    t_launch = time.time()
    rc = procs.run_tagged(
        argv, ctx["root"], env, "%d.%s" % (os.getpid(), cell["name"]),
        cell["spec"]["rank_limit_s"], log_path)
    return rc, t_launch


def check_gradients(scratch, size):
    """(ok, detail): on every rank, the sampled entries of every reduced
    gradient equal the float64 mean of the ranks' local ones."""
    local = [np.load(os.path.join(scratch, "grads_local_%d.npy" % r))
             for r in range(size)]
    want = np.mean(np.stack(local).astype(np.float64), axis=0)
    scale = np.abs(want).max()
    worst = 0.0
    for r in range(size):
        got = np.load(os.path.join(scratch, "grads_reduced_%d.npy" % r))
        worst = max(worst, float(np.abs(got - want).max()))
    differ = max(float(np.abs(a - local[0]).max()) for a in local[1:])
    ok = worst <= GRAD_RTOL * scale and differ > 0
    return ok, ("max |reduced - mean| %.3g of max |mean| %.3g over %d "
                "entries x %d ranks; ranks' own gradients differ by %.3g"
                % (worst, scale, want.size, size, differ))


def run(ctx):
    cell = ctx["cell"]
    size = cell["chips"]
    scratch = os.path.join(ctx["out_dir"], "scratch")
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    log_path = os.path.join(ctx["out_dir"], "world.log")
    rc, t_launch = launch(ctx, scratch, log_path)

    ranks = []
    for r in range(size):
        path = os.path.join(scratch, "rank_%d.json" % r)
        if os.path.exists(path):
            with open(path) as f:
                ranks.append(json.load(f))
    if len(ranks) < size or rc != 0:
        with open(log_path, errors="replace") as f:
            tail = f.read()[-6000:]
        sys.stderr.write(tail)
        if "yardstick rank: refused" in tail or not ranks:
            # Wrong platform or too few chips, or nothing ran at all: no
            # result line.
            raise measure.Refused("the world ended with %s and %d of %d "
                                  "ranks reported; see %s"
                                  % (rc, len(ranks), size, log_path))
    want = "cpu" if ctx["rehearsal"] else "tpu"
    for rk in ranks:
        if rk["device"]["platform"] != want or rk["device"]["count"] != size:
            raise measure.Refused("rank %d ran on %s" % (rk["rank"],
                                                         rk["device"]))

    r0 = ranks[0]
    steps = min(sum(n for _, n, _ in rk["chunks"]) for rk in ranks)
    tol = r0["loss_rtol"]
    grads_ok, grads_detail = check_gradients(scratch, size) \
        if len(ranks) == size else (False, "a rank is missing")
    sums = [rk["checksums"] for rk in ranks]
    in_window = sum(rk["compiles_in_window"] for rk in ranks)
    checks = {
        "every rank finished (launcher exit %s, %d of %d)"
        % (rc, len(ranks), size): rc == 0 and len(ranks) == size,
        "losses finite": all(np.isfinite(
            [rk["losses"]["first"], rk["losses"]["warm"]]
            + [c[2] for c in rk["chunks"]]).all() for rk in ranks),
        "lowest loss after step 0 below step 0's on every rank": all(
            measure.loss_fell(rk["losses"]["first"], [rk["losses"]["warm"]]
                              + [c[2] for c in rk["chunks"]])
            for rk in ranks),
        "step-0 loss within %g of the plain reference on every rank (%s)"
        % (tol, ["%.6g/%.6g" % (rk["losses"]["first"],
                                rk["losses"]["reference"]) for rk in ranks]):
            all(abs(rk["losses"]["first"] - rk["losses"]["reference"])
                <= tol * abs(rk["losses"]["reference"]) for rk in ranks),
        "reduced gradients are the mean of the ranks' own: " + grads_detail:
            grads_ok,
        "parameter checksums equal on all ranks (%s)" % sums[0]:
            all(s == sums[0] for s in sums),
        "no compile in the window (%d over all ranks)" % in_window:
            in_window == 0,
    }
    failed = measure.failed_steps(r0["chunks"])
    if len(ranks) < size or rc != 0:
        failed = max(failed, 1)      # a rank died or was killed
    return {
        "device": r0["device"],
        "chips": size,
        "samples_per_step": sum(rk["samples_per_step"] for rk in ranks),
        "flops_per_sample": r0["flops_per_sample"],
        "grad_bytes": r0["grad_bytes"],
        "kernels": r0["kernels"],
        "t_start": ctx["t_start"], "t_launch": t_launch,
        "t_init": [rk["t_init"] for rk in ranks],
        "t_window": max(rk["t_window"] for rk in ranks),
        "first_step_s": max(rk["first_step_s"] for rk in ranks),
        "cache": {k: sum(rk["cache"][k] for rk in ranks)
                  for k in r0["cache"]},
        "compiles_in_window": in_window,
        # Ranks move in lockstep; rank 0's chunks are the readings.
        "chunks": [c[:2] for c in r0["chunks"]],
        "window_s": max(rk["window_s"] for rk in ranks),
        "steps": steps,
        "failed_steps": failed,
        "losses": r0["losses"],
        "checks": checks,
        "peak_bytes": [b for rk in ranks for b in rk["peak_bytes"]],
        "counters": r0["counters"],
        "traced": r0["traced"],
    }
