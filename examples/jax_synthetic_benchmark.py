"""Synthetic throughput benchmark (reference:
examples/pytorch/pytorch_synthetic_benchmark.py — the img/sec harness
behind docs/benchmarks.rst): ResNet-50 forward+backward+allreduce on
random data, printing img/sec per iteration.

    python examples/jax_synthetic_benchmark.py --batch-size 32
"""

import _path_setup  # noqa: F401  (repo-checkout imports)

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu.jax as hvd


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-rank batch size")
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--num-warmup", type=int, default=2)
    p.add_argument("--image-size", type=int, default=224)
    args = p.parse_args()

    hvd.init()
    from horovod_tpu.models.resnet import (create_resnet50,
                                           resnet_loss_fn)
    model = create_resnet50()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, args.image_size, args.image_size, 3),
                                  jnp.bfloat16))

    def loss_fn(prm, batch):
        # Throughput harness: batch_stats updates are dropped, matching
        # the reference benchmark's loss-only step.
        loss, _ = resnet_loss_fn(model, prm, batch, train=True)
        return loss

    opt = optax.sgd(0.01, momentum=0.9)
    step, opt_init = hvd.make_data_parallel_step(
        loss_fn, opt, compression=hvd.Compression.bf16)
    params = hvd.broadcast_parameters(params, root_rank=0)
    # Placed like the step returns it, or the second step recompiles.
    opt_state = hvd.broadcast_optimizer_state(opt_init(params))

    world = hvd.size()
    global_bs = args.batch_size * world
    rng = np.random.RandomState(0)
    imgs = jnp.asarray(rng.randn(
        global_bs, args.image_size, args.image_size, 3),
        dtype=jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, size=(global_bs,)))
    batch = {"x": imgs, "y": labels}

    times = []
    for it in range(args.num_warmup + args.num_iters):
        t0 = time.time()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready(loss)
        dt = time.time() - t0
        if it >= args.num_warmup:
            times.append(dt)
            if hvd.rank() == 0:
                print("iter %d: %.1f img/sec" % (it, global_bs / dt))
    if hvd.rank() == 0:
        med = float(np.median(times))
        print("total img/sec on %d ranks: %.1f (+- %.1f)"
              % (world, global_bs / med,
                 global_bs * float(np.std(times)) / med ** 2))
    hvd.shutdown()


if __name__ == "__main__":
    main()
