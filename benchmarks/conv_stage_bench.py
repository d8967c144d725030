"""Per-stage conv microbenchmark: where ResNet-50's MXU gap lives.

docs/benchmarks.md records the conv stack at ~32% of datasheet peak
end to end; this harness measures each distinct conv SHAPE in the
ResNet-50 step in isolation — forward and fwd+bwd — so the "early
stages tile poorly" claim carries per-stage numbers and a candidate
kernel (Pallas implicit GEMM) can be judged against the stage it
targets.

Timing notes:
* every call differs: the WEIGHT carries a data-dependent perturbation
  from the previous call (w is tiny, so the perturbation itself is
  free), so no two executions have identical operands.
* per-op cost is DIFFERENTIAL (iters vs 2*iters), which cancels the
  blocking scalar fetch that ends each window; each conv is consumed
  by a ~1/256 strided-slice sum, not a full read.

    python benchmarks/conv_stage_bench.py [--batch 128] [--bwd]

Prints one JSON line per stage with sustained TFLOP/s and % of the
datasheet peak.

CAVEAT: not measured on the current stack.  Sub-millisecond per-op
numbers from a host clock are noise-limited; for adopt/reject
decisions use
``benchmarks/conv_ablation_bench.py``: it measures conv cost IN SITU
(whole-step ablation A/B, ±0.1 ms reproducible), which is also the
only cost a faster kernel can actually recover.
"""

import argparse
import json
import time

import numpy as np

DATASHEET_TFLOPS = 197.0  # v5e bf16

# (name, H_in, Cin, Cout, k, stride, count_per_fwd) — each distinct
# conv shape in the ResNet-50 forward.
STAGES = [
    ("stem7x7/2", 224, 3, 64, 7, 2, 1),
    # stage 1 (56²): entry 1x1 is 64ch only in block 1; blocks 2-3
    # take the 256ch block output.
    ("s1.1x1a", 56, 64, 64, 1, 1, 1),
    ("s1.1x1a'", 56, 256, 64, 1, 1, 2),
    ("s1.3x3", 56, 64, 64, 3, 1, 3),
    ("s1.1x1b", 56, 64, 256, 1, 1, 3),
    ("s1.proj", 56, 64, 256, 1, 1, 1),
    # stage 2 (56²->28²)
    ("s2.1x1a", 56, 256, 128, 1, 1, 1),
    ("s2.1x1a'", 28, 512, 128, 1, 1, 3),
    ("s2.3x3/2", 56, 128, 128, 3, 2, 1),
    ("s2.3x3", 28, 128, 128, 3, 1, 3),
    ("s2.1x1b", 28, 128, 512, 1, 1, 4),
    ("s2.proj/2", 56, 256, 512, 1, 2, 1),
    # stage 3 (28²->14²)
    ("s3.1x1a", 28, 512, 256, 1, 1, 1),
    ("s3.1x1a'", 14, 1024, 256, 1, 1, 5),
    ("s3.3x3/2", 28, 256, 256, 3, 2, 1),
    ("s3.3x3", 14, 256, 256, 3, 1, 5),
    ("s3.1x1b", 14, 256, 1024, 1, 1, 6),
    ("s3.proj/2", 28, 512, 1024, 1, 2, 1),
    # stage 4 (14²->7²)
    ("s4.1x1a", 14, 1024, 512, 1, 1, 1),
    ("s4.1x1a'", 7, 2048, 512, 1, 1, 2),
    ("s4.3x3/2", 14, 512, 512, 3, 2, 1),
    ("s4.3x3", 7, 512, 512, 3, 1, 2),
    ("s4.1x1b", 7, 512, 2048, 1, 1, 3),
    ("s4.proj/2", 14, 1024, 2048, 1, 2, 1),
]


def stage_flops(batch, h, cin, cout, k, stride, bwd):
    ho = h // stride
    f = 2.0 * batch * ho * ho * cin * cout * k * k
    return f * (3.0 if bwd else 1.0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--bwd", action="store_true",
                    help="measure fwd+bwd (grads wrt x and w)")
    ap.add_argument("--only", default=None,
                    help="comma list of stage names to run")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    only = set(args.only.split(",")) if args.only else None
    results = []
    picked = [s for s in STAGES if not only or s[0] in only]
    for name, h, cin, cout, k, stride, count in picked:
        n = args.batch
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(n, h, h, cin), jnp.bfloat16)
        w = jnp.asarray(rng.randn(k, k, cin, cout) * 0.05, jnp.bfloat16)

        def conv(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (stride, stride),
                "SAME" if k > 1 else "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        # Each call carries a fixed dispatch overhead, and no two
        # executions may share operands.  So: every
        # conv gets a per-instance bf16-visible weight modulation (a
        # 1e-30 nudge rounds away at bf16's 2^-8 epsilon), U convs
        # run per call to amortize the overhead, and the per-conv
        # cost is the difference of min-regression slopes at U=8 and
        # U=1 over 7 — call overhead cancels exactly.
        def make(U):
            def step(i, s):
                for j in range(U):
                    wi = w * (jnp.bfloat16(1.05)
                              + jnp.bfloat16(0.5)
                              * jnp.sin(i + jnp.float32(j))
                              .astype(jnp.bfloat16))
                    if args.bwd:
                        def loss(xi, wj):
                            return conv(xi, wj).astype(
                                jnp.float32).sum()
                        l, (dx, dw) = jax.value_and_grad(
                            loss, argnums=(0, 1))(x, wi)
                        s = s + l + dw.astype(jnp.float32).sum() \
                            + dx[:, ::16, ::16, :].astype(
                                jnp.float32).sum()
                    else:
                        y = conv(x, wi)
                        s = s + y[:, ::16, ::16, :].astype(
                            jnp.float32).sum()
                return s
            return jax.jit(step)

        fetch = jax.jit(lambda v: v.astype(jnp.float32))
        seq = [0]

        def slope(fn, iters):
            def run(N):
                s = jnp.float32(0.0)
                t0 = time.perf_counter()
                for _ in range(N):
                    seq[0] += 1
                    s = fn(jnp.float32(seq[0]), s)
                float(np.asarray(fetch(s)))
                return time.perf_counter() - t0
            run(4)  # compile + warm
            lengths = (0, iters, 2 * iters)
            mins = [min(run(L) for _ in range(3)) for L in lengths]
            lx = np.asarray(lengths, np.float64)
            ly = np.asarray(mins, np.float64)
            return float(
                ((lx - lx.mean()) * (ly - ly.mean())).sum()
                / ((lx - lx.mean()) ** 2).sum())

        s1 = slope(make(1), args.iters)
        s8 = slope(make(8), max(args.iters // 2, 10))
        per_op = max((s8 - s1) / 7.0, 1e-9)
        flops = stage_flops(n, h, cin, cout, k, stride, args.bwd)
        tflops = flops / per_op / 1e12
        rec = {"stage": name, "x": [n, h, h, cin],
               "w": [k, k, cin, cout], "stride": stride,
               "count_per_fwd": count,
               "time_us": round(per_op * 1e6, 1),
               "tflops": round(tflops, 1),
               "pct_peak": round(100 * tflops / DATASHEET_TFLOPS, 1),
               "mode": "fwd+bwd" if args.bwd else "fwd"}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if results:
        tot_t = sum(r["time_us"] * r["count_per_fwd"] for r in results)
        tot_f = sum(stage_flops(args.batch, s[1], s[2], s[3], s[4],
                                s[5], args.bwd) * s[6]
                    for s in picked)
        print(json.dumps({
            "summary": "weighted", "total_us": round(tot_t, 1),
            "agg_tflops": round(tot_f / (tot_t * 1e-6) / 1e12, 1),
            "agg_pct_peak": round(
                100 * tot_f / (tot_t * 1e-6) / 1e12 / DATASHEET_TFLOPS,
                1)}))


if __name__ == "__main__":
    main()
