"""Transformer training throughput (tokens/sec), single chip.

Companion to ``bench.py`` (ResNet-50 img/sec — the reference's headline
workload): measures the transformer family with the Pallas flash
attention this framework uses on TPU, at a sequence length where the
O(seq²) HBM cost of unfused attention bites.

    python benchmarks/transformer_bench.py [--seq 2048] [--flash 0|1]

Prints one JSON line.  ``--flash 0`` reruns with the XLA-fused
attention for an A/B on the same model.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--flash", default=None,
                    help="force HOROVOD_FLASH_ATTENTION")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--fused", type=int, default=0,
                    help="fused qkv + gate projections (A/B lever; "
                         "measured rejection at d1024 — see "
                         "docs/benchmarks.md — so off by default)")
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots", "dots_no_batch"],
                    help="layer remat policy (A/B lever)")
    ap.add_argument("--opt-split", type=int, default=0,
                    help="compile backward and optimizer update as TWO "
                         "programs (anti-lever: measures what fusing "
                         "the update into the step is worth)")
    ap.add_argument("--collective-matmul", type=int, default=0,
                    help="latency-hiding TP matmul ring (no-op at "
                         "tp=1; single-chip neutrality check)")
    args = ap.parse_args()
    if args.d_model % args.head_dim:
        raise SystemExit("--head-dim %d does not divide --d-model %d"
                         % (args.head_dim, args.d_model))
    if args.flash is not None:
        os.environ["HOROVOD_FLASH_ATTENTION"] = args.flash

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params,
                                                make_train_step)

    cfg = TransformerConfig(
        vocab_size=8192, d_model=args.d_model, n_layers=args.layers,
        n_heads=args.d_model // args.head_dim,
        n_kv_heads=args.d_model // args.head_dim,
        d_ff=args.d_model * 3, max_seq=args.seq,
        fused_qkv=bool(args.fused), fused_gate=bool(args.fused),
        remat=args.remat != "none",
        remat_policy=args.remat if args.remat != "none" else "full",
        collective_matmul=bool(args.collective_matmul))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch, args.seq)),
        jnp.int32)
    params_host = init_params(jax.random.PRNGKey(0), cfg)
    build, shard_batch = make_train_step(
        cfg, mesh, optax.adam(1e-3),
        split_optimizer=bool(args.opt_split))
    step, params, opt_state = build(params_host)
    batch = shard_batch({"tokens": tokens, "targets": tokens})
    fetch = jax.jit(lambda v: v.astype(jnp.float32))

    def run(n, p, o):
        """n steps ending in a fetch of the loss to the host, so the
        wall time covers exactly this work."""
        t0 = time.perf_counter()
        loss = None
        for _ in range(n):
            p, o, loss = step(p, o, batch)
        float(np.asarray(fetch(loss)))
        return time.perf_counter() - t0, p, o

    # warmup compiles both step and fetch; the measured run then has no
    # compile or cold-dispatch component
    _, params, opt_state = run(3, params, opt_state)
    best = float("inf")
    for _ in range(3):
        dt, params, opt_state = run(args.steps, params, opt_state)
        best = min(best, dt)
    tok_s = args.batch * args.seq * args.steps / best
    print(json.dumps({
        "metric": "transformer_tokens_per_sec_per_chip",
        "value": round(tok_s, 1), "unit": "tokens/sec",
        "seq": args.seq,
        "flash": os.environ.get("HOROVOD_FLASH_ATTENTION", "auto"),
    }))


if __name__ == "__main__":
    main()
