"""ResNet-50 on synthetic ImageNet batches: upstream Horovod's synthetic
benchmark, through this framework's entry points.

What a builder gives a job: the framework's step (``jit_step``) or the
pieces of an eager loop (``eager_parts``), both with a batch and weights
made from the seed, the operations a sample needs, and ``reference_loss``,
a plain float32 ``jax.numpy`` forward pass that shares no code with
``horovod_tpu/models/resnet.py``.
"""

import numpy as np

from yardstick import flops

# bf16 activations through 53 convolutions against a float32 reference:
# each rounding is 2^-9 relative, BatchNorm renormalises every layer, and
# the loss averages 128 images a worker.  On the chip the two differ by
# 1e-5 of the loss or less (my chip runs, PR 22); 2e-3 leaves room for other
# seeds and none for a wrong layer, stride or label (tenths of the loss) or
# for activations kept below bf16.  The tiny size of the CPU rehearsal
# (32-pixel images, 4 a worker) is ill-conditioned and brings its own,
# wider tolerance in the configuration's "tiny".
LOSS_RTOL = 0.002


def _sizes(cell):
    config, spec = cell["config"], cell["spec"]
    return {"image": config["image_size"], "classes": config["num_classes"],
            "batch_per_chip": spec["batch_per_chip"]}


def _model_and_loss(cell):
    import jax.numpy as jnp

    from horovod_tpu.models.resnet import ResNet, resnet_loss_fn
    config = cell["config"]
    model = ResNet(depth=config["depth"], num_classes=config["num_classes"],
                   dtype=jnp.dtype(config["activation_dtype"]))

    def loss_fn(variables, batch):
        # The loss-only step of examples/jax_synthetic_benchmark.py and
        # chip_smoke.py: batch statistics are used, their running
        # averages are not carried.
        return resnet_loss_fn(model, variables, batch, train=True)[0]

    return model, loss_fn


def _variables(cell, model, seed):
    """Weights on the device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp
    size = cell["config"]["image_size"]
    variables = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, size, size, 3), model.dtype)))(
            jax.random.PRNGKey(seed))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        variables["params"]))
    want = cell["config"].get("parameters")
    if want is not None and n != want:
        raise ValueError("ResNet has %d parameters, the configuration says "
                         "%d" % (n, want))
    return variables


def make_batch(cell, seed, samples):
    """One synthetic batch on the host, as upstream's benchmark makes
    it: normal pixels, uniform labels."""
    import jax.numpy as jnp
    s = _sizes(cell)
    rng = np.random.default_rng(seed)
    return {"x": rng.standard_normal(
                (samples, s["image"], s["image"], 3), np.float32
            ).astype(jnp.bfloat16),
            "y": rng.integers(0, s["classes"], samples, np.int32)}


def _optimizer(cell):
    import optax
    opt = cell["config"]["optimizer"]
    assert opt["name"] == "sgd", opt
    return optax.sgd(opt["learning_rate"], momentum=opt["momentum"])


def _facts(cell, variables, samples_per_step):
    import jax
    return {
        "samples_per_step": samples_per_step,
        "flops_per_sample": flops.resnet50_train_flops(
            cell["config"]["image_size"]),
        "grad_bytes": sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(variables["params"])),
        "kernels": [],
        "loss_rtol": cell["config"].get("loss_rtol", LOSS_RTOL),
    }


def jit_step(cell, seed, hvd, devices):
    """``hvd.make_data_parallel_step`` over ``devices``, as
    ``chip_smoke.py leg_resnet50`` drives it."""
    model, loss_fn = _model_and_loss(cell)
    variables = _variables(cell, model, seed)
    n = cell["spec"]["batch_per_chip"] * len(devices)
    host_batch = make_batch(cell, seed, n)
    step, opt_init = hvd.make_data_parallel_step(loss_fn, _optimizer(cell))
    facts = _facts(cell, variables, n)
    variables = hvd.broadcast_parameters(variables, root_rank=0)
    # Placed like the step returns it, or the second step compiles again.
    opt_state = hvd.broadcast_optimizer_state(opt_init(variables))
    batch = hvd.shard_batch(host_batch)

    def run_step(state, batch):
        variables, opt_state, loss = step(state[0], state[1], batch)
        return (variables, opt_state), loss

    return dict(facts, step=run_step, state=(variables, opt_state),
                batch=batch,
                reference=lambda state: reference_loss(
                    state[0], host_batch, shards=len(devices)),
                probe=lambda state: state[0]["params"]["Dense_0"]["bias"])


def eager_parts(cell, seed, rank):
    """The pieces of the loop upstream's users write: a local gradient
    function, the optimizer to wrap, this rank's own batch.  Gradients are
    those of the parameters; batch statistics are no parameters and are
    not exchanged, as in upstream's benchmark."""
    import jax
    model, loss_fn = _model_and_loss(cell)
    variables = _variables(cell, model, seed)        # same on every rank
    n = cell["spec"]["batch_per_chip"]
    host_batch = make_batch(cell, seed * 1000 + rank, n)
    stats = variables["batch_stats"]

    def params_loss(params, batch):
        return loss_fn({"params": params, "batch_stats": stats}, batch)

    facts = _facts(cell, variables, n)
    return dict(facts, loss_fn=params_loss, params=variables["params"],
                optimizer=_optimizer(cell),
                batch=jax.device_put(host_batch),
                reference=lambda params: reference_loss(
                    {"params": params, "batch_stats": stats}, host_batch))


# -- the plain reference ---------------------------------------------------

def reference_loss(variables, host_batch, shards=1):
    """Cross entropy of ResNet-50 v1.5 in training mode, float32 at the
    highest matmul precision, written from the paper's table: 7x7/2
    convolution, 3x3/2 max pool, four stages of bottleneck blocks
    (1x1, 3x3, 1x1 with 4x expansion, stride in the 3x3, a projection
    where the shape changes), global average pool, dense.  BatchNorm uses
    the batch's own statistics; data-parallel workers each normalise over
    their own ``1/shards`` of the batch, as upstream's do, and the loss is
    the mean over workers.  Reads the parameters by the names flax gave
    them and nothing else of the program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def conv(x, kernel, stride, padding="SAME"):
        return lax.conv_general_dilated(
            x, kernel.astype(jnp.float32), (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)

    def norm(x, p, relu=True, residual=None):
        mean = x.mean((0, 1, 2))
        var = jnp.maximum((x * x).mean((0, 1, 2)) - mean * mean, 0.0)
        y = (x - mean) * lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
        if residual is not None:
            y = y + residual
        return jnp.maximum(y, 0.0) if relu else y

    def forward(params, x, labels):
        x = conv(x.astype(jnp.float32), params["Conv_0"]["kernel"], 2,
                 [(3, 3), (3, 3)])
        x = norm(x, params["NormAct_0"])
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        block = 0
        for stage, n_blocks in enumerate((3, 4, 6, 3)):
            for j in range(n_blocks):
                p = params["BottleneckBlock_%d" % block]
                stride = 2 if stage > 0 and j == 0 else 1
                y = norm(conv(x, p["Conv_0"]["kernel"], 1), p["NormAct_0"])
                y = norm(conv(y, p["Conv_1"]["kernel"], stride),
                         p["NormAct_1"])
                y = conv(y, p["Conv_2"]["kernel"], 1)
                last = "NormAct_2"
                if "Conv_3" in p:
                    x = norm(conv(x, p["Conv_3"]["kernel"], stride),
                             p["NormAct_2"], relu=False)
                    last = "NormAct_3"
                x = norm(y, p[last], residual=x)
                block += 1
        x = x.mean((1, 2))
        logits = jnp.dot(x, params["Dense_0"]["kernel"],
                         precision=lax.Precision.HIGHEST) \
            + params["Dense_0"]["bias"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()

    def mean_over_workers(params, x, labels):
        return lax.map(lambda xy: forward(params, *xy), (x, labels)).mean()

    device = sorted(jax.tree.leaves(variables)[0].devices(),
                    key=lambda d: d.id)[0]
    x, y = host_batch["x"], host_batch["y"]
    return float(jax.jit(mean_over_workers)(
        jax.device_put(variables["params"], device),
        jax.device_put(x.reshape((shards, -1) + x.shape[1:]), device),
        jax.device_put(y.reshape(shards, -1), device)))


# -- compiled for a chip that is not attached (rehearse.py compile) --------

def aot_step(cell, devices):
    """[(label, jitted, abstract arguments)] of the cell's device programs
    over described ``devices``.  ``make_data_parallel_step`` takes its
    mesh from the running world, so the rehearsal steers the one function
    that says which; everything else is the framework's own builder."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu.jax.data_parallel as dp
    from horovod_tpu.jax import spmd
    model, loss_fn = _model_and_loss(cell)
    s = _sizes(cell)
    shapes = jax.eval_shape(
        lambda key: model.init(key, jnp.zeros(
            (1, s["image"], s["image"], 3), model.dtype)),
        jax.random.PRNGKey(0))
    eager = cell["job"] == "eager_world"
    mesh = Mesh(np.asarray(devices[:1] if eager else devices),
                (spmd.DEFAULT_AXIS,))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(spmd.DEFAULT_AXIS))

    def on(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    n = s["batch_per_chip"] * (1 if eager else len(devices))
    batch = on({"x": jax.ShapeDtypeStruct((n, s["image"], s["image"], 3),
                                          jnp.bfloat16),
                "y": jax.ShapeDtypeStruct((n,), jnp.int32)}, rows)
    if eager:
        # One rank's own programs; the exchange between them is the
        # engine's and is compiled at run time by size class.
        stats = shapes["batch_stats"]
        grad = jax.jit(jax.value_and_grad(
            lambda p, st, b: loss_fn({"params": p, "batch_stats": st}, b)))
        return [("one rank's value_and_grad", grad,
                 (on(shapes["params"], rep), on(stats, rep), batch))]
    dp._world_mesh = lambda: mesh
    step, opt_init = dp.make_data_parallel_step(loss_fn, _optimizer(cell))
    return [("make_data_parallel_step", step,
             (on(shapes, rep), on(jax.eval_shape(opt_init, shapes), rep),
              batch))]
