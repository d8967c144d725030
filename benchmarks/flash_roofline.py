"""Flash-kernel roofline sweep — the one-command lever table.

VERDICT r5 named the flash kernels (~40% of the calibrated matmul
rate) as the last single-chip perf lever.  This harness produces the
evidence for the measured lever table in docs/benchmarks.md in one
command:

1. calibrates the chip's matmul roofline (an 8192^3 bf16 matmul
   probe — the honest denominator: the rate a perfect MXU-bound kernel
   could sustain),
2. sweeps every VMEM-feasible (block_q, block_k) pair at the flagship
   attention shape via ``autotune_flash_blocks`` (fwd and bwd TFLOP/s
   per candidate, the kernel-parameter leg of the autotune plane),
3. A/Bs the backward STRUCTURE at the winning blocks: two-pass dq/dkv
   kernels vs the one kernel (dq of a whole head kept in VMEM) vs the
   chunked-XLA escape hatch — end to end through ``jax.grad`` of the
   public ``flash_attention``, exactly what a train step runs.

Prints one JSON line per measurement plus a summary; ``--markdown``
additionally emits the docs-ready lever table.

    # flagship shape on the chip
    python benchmarks/flash_roofline.py --markdown
    # CPU smoke of the harness schema (interpret mode, tiny shape)
    python benchmarks/flash_roofline.py --cpu-smoke
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BWD_VARIANTS = ("pallas", "pallas_onepass", "chunked")


def probe_peak_flops(jax, jnp):
    """Calibrated roofline: sustained rate of a large bf16 matmul
    chain, by differential (2N - N) windows that each end in a fetch of
    one scalar of the last product."""
    import numpy as np
    n = 1024 if jax.devices()[0].platform == "cpu" else 8192
    a = jnp.ones((n, n), jnp.bfloat16)
    b = (jnp.eye(n, dtype=jnp.float32) * 1.0001).astype(jnp.bfloat16)
    f = jax.jit(lambda a, b: a @ b)
    fetch = jax.jit(lambda v: v[0, 0].astype(jnp.float32))
    float(np.asarray(fetch(f(a, b))))

    def run(k):
        t0 = time.perf_counter()
        c = a
        for _ in range(k):
            c = f(c, b)
        float(np.asarray(fetch(c)))
        return time.perf_counter() - t0

    run(5)
    t1, t2 = run(10), run(20)
    dt = max((t2 - t1) / 10, 1e-9)
    return 2 * n ** 3 / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--batch-heads", type=int, default=32,
                    help="flattened batch*heads (flagship: b4 x h8)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--markdown", action="store_true",
                    help="emit the docs/benchmarks.md lever table")
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="tiny interpret-mode run validating the "
                         "harness (no chip needed)")
    args = ap.parse_args()
    if args.cpu_smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.seq, args.d, args.batch_heads, args.iters = 128, 32, 2, 1

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import pallas_kernels as pk

    causal = bool(args.causal)
    dtype = jnp.dtype(args.dtype)
    roof = probe_peak_flops(jax, jnp)  # calibrated matmul rate
    print(json.dumps({"metric": "matmul_roofline_tflops",
                      "value": round(roof / 1e12, 1)}))

    # -- block sweep (fwd + two-pass bwd TFLOP/s per candidate) --------
    # CPU smoke: two candidates validate the schema; interpret-mode
    # timings are meaningless anyway, so don't pay for the full grid.
    cands = ([(64, 64), (128, 128)] if args.cpu_smoke else None)
    sweep = pk.autotune_flash_blocks(
        args.seq, args.d, batch_heads=args.batch_heads, dtype=dtype,
        causal=causal, iters=args.iters, candidates=cands,
        report_core=False, pin=False)
    for (bq, bk) in sweep["candidates"]:
        s = sweep["samples"][(bq, bk)]
        print(json.dumps({
            "metric": "flash_block_sweep", "block_q": bq, "block_k": bk,
            "fwd_tflops": round(s["fwd_tflops"], 2),
            "bwd_tflops": round(s["bwd_tflops"], 2),
            "fwd_frac_of_roofline": round(
                s["fwd_tflops"] * 1e12 / roof, 4),
            "bwd_frac_of_roofline": round(
                s["bwd_tflops"] * 1e12 / roof, 4)}))
    best_bq, best_bk = sweep["best"]

    # -- backward-structure A/B at the winning blocks ------------------
    # End to end through jax.grad of the public flash_attention: the
    # path a train step runs, variant selected exactly how a job
    # selects it (HVD_TPU_FLASH_BWD, read at trace time).
    b = max(1, args.batch_heads // 8)
    h = args.batch_heads // b
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, args.seq, h, args.d), dtype)
    k = jnp.asarray(rng.randn(b, args.seq, h, args.d), dtype)
    v = jnp.asarray(rng.randn(b, args.seq, h, args.d), dtype)
    tile_frac = 0.5 if causal else 1.0
    fwd_flops = 4.0 * b * h * args.seq * args.seq * args.d * tile_frac
    grad_flops = 3.5 * fwd_flops  # fwd (2 matmuls) + bwd (5 matmuls)

    os.environ["HVD_TPU_FLASH_BLOCK_Q"] = str(best_bq)
    os.environ["HVD_TPU_FLASH_BLOCK_K"] = str(best_bk)
    variant_rows = {}
    for variant in BWD_VARIANTS:
        os.environ["HVD_TPU_FLASH_BWD"] = variant

        def grad_step(q_, k_, v_):
            return jax.grad(lambda a, b_, c: jnp.sum(
                pk.flash_attention(a, b_, c, causal=causal)
                .astype(jnp.float32)), argnums=(0, 1, 2))(q_, k_, v_)

        fn = jax.jit(grad_step)
        try:
            t = pk._time_device(fn, (q, k, v), args.iters)
        except Exception as exc:  # noqa: BLE001 - report, keep sweeping
            print(json.dumps({"metric": "flash_bwd_variant",
                              "variant": variant, "error": str(exc)}))
            continue
        tflops = grad_flops / t / 1e12
        variant_rows[variant] = tflops
        print(json.dumps({
            "metric": "flash_bwd_variant", "variant": variant,
            "block_q": best_bq, "block_k": best_bk,
            "ms": round(t * 1e3, 3),
            "fwd_bwd_tflops": round(tflops, 2),
            "frac_of_roofline": round(tflops * 1e12 / roof, 4)}))
    for key in ("HVD_TPU_FLASH_BLOCK_Q", "HVD_TPU_FLASH_BLOCK_K",
                "HVD_TPU_FLASH_BWD"):
        os.environ.pop(key, None)

    best_variant = (max(variant_rows, key=variant_rows.get)
                    if variant_rows else None)
    best_sample = sweep["samples"][(best_bq, best_bk)]
    summary = {
        "metric": "flash_roofline",
        "seq": args.seq, "d": args.d, "causal": causal,
        "matmul_roofline_tflops": round(roof / 1e12, 1),
        "best_block_q": best_bq, "best_block_k": best_bk,
        "best_fwd_frac_of_roofline": round(
            best_sample["fwd_tflops"] * 1e12 / roof, 4),
        "best_bwd_frac_of_roofline": round(
            best_sample["bwd_tflops"] * 1e12 / roof, 4),
        "best_bwd_variant": best_variant,
        "smoke": bool(args.cpu_smoke),
    }
    print(json.dumps(summary))

    if args.markdown:
        print()
        print("| lever | measured (TFLOP/s, frac of %.0f TFLOP/s "
              "matmul roofline) | verdict |" % (roof / 1e12))
        print("|---|---|---|")
        for (bq, bk) in sweep["candidates"]:
            s = sweep["samples"][(bq, bk)]
            mark = " **<- winner**" if (bq, bk) == (best_bq,
                                                   best_bk) else ""
            print("| blocks (%d, %d) | fwd %.1f (%.0f%%), bwd %.1f "
                  "(%.0f%%) |%s |"
                  % (bq, bk, s["fwd_tflops"],
                     100 * s["fwd_tflops"] * 1e12 / roof,
                     s["bwd_tflops"],
                     100 * s["bwd_tflops"] * 1e12 / roof, mark))
        for variant, tflops in sorted(variant_rows.items(),
                                      key=lambda kv: -kv[1]):
            mark = " **<- winner**" if variant == best_variant else ""
            print("| bwd structure `%s` | fwd+bwd %.1f (%.0f%%) |%s |"
                  % (variant, tflops, 100 * tflops * 1e12 / roof,
                     mark))


if __name__ == "__main__":
    main()
