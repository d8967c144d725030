#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 yardstick/run.py --workload CELL --seed N --seconds S --trace 0|1

Looks the cell up in ``BENCHMARK.json``, hands it to the job its file
names (``jobs/<job>.py``), and prints as the last line of standard output
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``.  It measures on a TPU with the
cell's number of chips and nowhere else: anything else exits non-zero and
prints no result.  ``rehearse.py`` is the entry point for the CPU.

This process imports jax only inside a one-process job; the parent of a
multi-process cell never does, because a parent that has touched JAX holds
the chip its children need.
"""

import time

T_START = time.time()           # set-up counts from here

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(workload, seed, seconds, trace, rehearsal=False,
             keep_trace_json=None):
    """The result line of one run, as a dict.  A rehearsal runs the cell
    at its tiny size, on the CPU."""
    from yardstick import manifest as mf
    from yardstick import report
    manifest = mf.load()
    cell = manifest.cell(workload, tiny=rehearsal)
    out_dir = os.path.join(ROOT, "yardstick_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = {"manifest": manifest, "cell": cell, "seed": seed,
           "seconds": seconds, "trace": trace, "rehearsal": rehearsal,
           "t_start": T_START,
           "out_dir": out_dir, "root": ROOT,
           "keep_trace_json": keep_trace_json}
    evidence = manifest.module("jobs", cell["job"]).run(ctx)
    return report.result_line(manifest, workload, evidence, trace, rehearsal)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--keep-trace-json", default=None,
                   help="also write the trace, as trace.py reads it, here")
    args = p.parse_args(argv)
    from yardstick.manifest import ManifestError
    from yardstick.measure import Refused
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace),
                        keep_trace_json=args.keep_trace_json)
    except (ManifestError, Refused) as exc:
        print("yardstick: no result: %s" % exc, file=sys.stderr)
        return 2
    if not line["correct"]:
        # The result line says only that; the log should say which check.
        print("yardstick: not correct: %s" % "; ".join(
            name for name, held in line["checks"].items() if not held),
            file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
