"""Multihost (pod) training: one process per host, control on TCP,
payloads on ICI/DCN.

The pod execution mode (docs/architecture.md): every process joins ONE
global JAX runtime; the native core negotiates collective order over
the hosts' TCP plane while tensor bytes move as compiled XLA
collectives over the global device mesh.  Shows both API levels:

* the jit path — ``make_data_parallel_step`` over the global mesh,
  each process feeding its own batch shard (the fast path);
* the eager path — ``hvd.allreduce`` of a ``jax.Array``, which stays
  device-resident end to end (metric averaging, debugging, custom
  loops).

Run on a real pod with one process per host, on one TPU host with one
process per chip (the launcher binds each local slot to its own chip),
or locally on the CPU test world:

    JAX_PLATFORMS=cpu python -m horovod_tpu.runner -np 2 --multihost \
      python examples/multihost_pod_training.py
"""

import _path_setup  # noqa: F401  (repo-checkout imports)

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu.jax as hvd
from horovod_tpu.models.mlp import init_mlp, mlp_loss, synthetic_mnist


def main(steps: int = 20, batch_per_rank: int = 32, lr: float = 0.05):
    hvd.init()
    rank, world = hvd.rank(), hvd.size()
    print("rank %d/%d: %d local of %d global devices, %d processes"
          % (rank, world, len(jax.local_devices()), len(jax.devices()),
             jax.process_count()), flush=True)

    params0 = init_mlp(jax.random.PRNGKey(0))  # same seed everywhere
    step, opt_init = hvd.make_data_parallel_step(mlp_loss,
                                                 optax.sgd(lr))
    # Replicate params/optimizer state over the GLOBAL mesh (every
    # rank passes the same values; same seed makes them identical).
    params = hvd.replicate(params0)
    opt_state = hvd.replicate(opt_init(params0))

    # Reference semantics: every rank loads ITS OWN data.
    data = synthetic_mnist(np.random.RandomState(1234 + rank),
                           batch_per_rank * steps)
    xs, ys = data["x"], data["y"]

    loss = None
    for i in range(steps):
        lo = i * batch_per_rank
        batch = {"x": jnp.asarray(xs[lo:lo + batch_per_rank]),
                 "y": jnp.asarray(ys[lo:lo + batch_per_rank])}
        # Each process passes ITS shard; shard_batch assembles the
        # global array over the pod mesh.
        sharded = hvd.shard_batch(batch)
        params, opt_state, loss = step(params, opt_state, sharded)
        if i % 5 == 0:
            # Eager device-resident allreduce for the metric: the
            # jax.Array payload never transits the host.
            avg = hvd.allreduce(
                jnp.asarray([float(np.asarray(
                    hvd.data_parallel.fetch(loss)))]),
                op=hvd.Average, name="loss_avg")
            if rank == 0:
                print("step %d: mean loss %.4f"
                      % (i, float(np.asarray(avg)[0])), flush=True)

    print("DONE rank=%d size=%d" % (rank, world), flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
