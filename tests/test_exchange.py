"""The in-program gradient exchange reduces leaf by leaf (``jax/spmd.py``).

``grouped_allreduce`` / ``allreduce_pytree`` pack nothing: every leaf has
its own collective in its own dtype, and combining them is the compiler's.
``make_data_parallel_step`` over four devices computes what one device
fed the whole batch computes, and hands the CPU's compiler no option.
CPU world of virtual devices; what the chip makes of it is PERF.md's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu.jax as hvd
import horovod_tpu.jax.data_parallel as dp
from horovod_tpu.common import scopes
from horovod_tpu.jax import spmd
from horovod_tpu.jax.optimizer import allreduce_gradients

N = 4
AXIS = spmd.DEFAULT_AXIS
COMPRESSIONS = {"none": hvd.Compression.none, "fp16": hvd.Compression.fp16,
                "bf16": hvd.Compression.bf16}


def _mesh(n=N):
    return Mesh(np.asarray(jax.devices()[:n]), (AXIS,))


def _per_rank_tree():
    """Mixed shapes and dtypes, a leading axis of one row a rank."""
    rng = np.random.RandomState(0)
    return {
        "kernel": rng.randn(N, 3, 5).astype(np.float32),
        "bias": jnp.asarray(rng.randn(N, 7), jnp.bfloat16),
        "scalar": rng.randn(N).astype(np.float32),
        "steps": rng.randint(-9, 9, size=(N, 4)).astype(np.int32),
    }


def _on_each_rank(fn, tree):
    """``fn`` of every rank's own row of ``tree`` under ``shard_map``;
    the result is replicated, rank 0's is returned."""
    def body(t):
        return fn(jax.tree.map(lambda x: x[0], t))
    return jax.jit(jax.shard_map(body, mesh=_mesh(), in_specs=P(AXIS),
                                 out_specs=P(), check_vma=False))(tree)


def _numpy_reduce(rows, op, wire):
    """What one leaf's own reduce gives: cast to the wire, reduce across
    ranks, divide on the wire for an average, cast back."""
    dtype = rows.dtype
    floating = jnp.issubdtype(dtype, jnp.floating)
    on_wire = wire if wire is not None and floating else dtype
    x = np.asarray(jnp.asarray(rows).astype(on_wire), np.float64)
    if op in ("Sum", "Average"):
        red = np.asarray(jnp.asarray(x.sum(0)).astype(on_wire), np.float64)
        if op == "Average":
            red = red / N
    else:
        red = x.min(0) if op == "Min" else x.max(0)
    return np.asarray(jnp.asarray(red).astype(on_wire).astype(dtype))


@pytest.mark.parametrize("compression", sorted(COMPRESSIONS))
@pytest.mark.parametrize("op", ["Sum", "Average", "Min", "Max"])
def test_grouped_allreduce_equals_numpy_leaf_by_leaf(op, compression):
    tree = _per_rank_tree()
    leaves, treedef = jax.tree.flatten(tree)
    outs = _on_each_rank(
        lambda t: spmd.grouped_allreduce(
            jax.tree.leaves(t), op=op, axis_name=AXIS,
            compression=COMPRESSIONS[compression]), tree)
    assert len(outs) == len(leaves)
    for rows, out in zip(leaves, outs):
        assert out.dtype == rows.dtype and out.shape == rows.shape[1:]
        want = _numpy_reduce(rows, op, getattr(COMPRESSIONS[compression],
                                               "wire_dtype", None))
        # One rounding of the narrowest dtype on the path: XLA may keep
        # the sum of four bf16 values wider than NumPy's cast does.
        narrow = jnp.issubdtype(rows.dtype, jnp.floating) and (
            rows.dtype == jnp.bfloat16 or compression != "none")
        np.testing.assert_allclose(
            np.asarray(out, np.float64), np.asarray(want, np.float64),
            rtol=2e-2 if narrow else 1e-6, atol=2e-2 if narrow else 1e-6)
    # The pytree form is the same reduce with the structure put back.
    again = _on_each_rank(
        lambda t: spmd.allreduce_pytree(
            t, op=op, axis_name=AXIS,
            compression=COMPRESSIONS[compression]), tree)
    assert jax.tree.structure(again) == treedef
    for a, b in zip(jax.tree.leaves(again), outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _eqns(jaxpr, found):
    """(primitive name, name stack, input dtypes) of every equation,
    nested jaxprs included."""
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name, str(eqn.source_info.name_stack),
                      [v.aval.dtype for v in eqn.invars
                       if hasattr(v.aval, "dtype")]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _eqns(inner, found)
    return found


@pytest.mark.parametrize("compression", ["none", "bf16"])
def test_allreduce_gradients_is_one_psum_a_leaf_and_packs_nothing(
        compression):
    tree = {k: v for k, v in _per_rank_tree().items() if k != "steps"}

    def body(t):
        return allreduce_gradients(
            jax.tree.map(lambda x: x[0], t), axis_name=AXIS,
            compression=COMPRESSIONS[compression])

    mapped = jax.shard_map(body, mesh=_mesh(), in_specs=P(AXIS),
                           out_specs=P(), check_vma=False)
    eqns = _eqns(jax.make_jaxpr(mapped)(tree).jaxpr, [])
    psums = [e for e in eqns if e[0].startswith("psum")]
    assert len(psums) == len(tree)
    assert all(scopes.EXCHANGE in stack for _, stack, _ in psums)
    under = [name for name, stack, _ in eqns if scopes.EXCHANGE in stack]
    assert "concatenate" not in under and "dynamic_slice" not in under \
        and "slice" not in under
    # Each leaf goes out in its own dtype (or the codec's): nothing is
    # promoted to a neighbour's.
    wires = sorted(str(d) for _, _, dtypes in psums for d in dtypes)
    assert wires == (["bfloat16"] * 3 if compression == "bf16"
                     else ["bfloat16", "float32", "float32"])


def _mlp_problem():
    rng = np.random.RandomState(3)
    params = {"w1": rng.randn(6, 16).astype(np.float32) * 0.3,
              "b1": np.zeros((16,), np.float32),
              "w2": rng.randn(16, 3).astype(np.float32) * 0.3,
              "b2": np.zeros((3,), np.float32)}
    batch = {"x": rng.randn(8 * N, 6).astype(np.float32),
             "y": rng.randn(8 * N, 3).astype(np.float32)}

    def loss_fn(params, batch):
        hidden = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
        return jnp.mean((hidden @ params["w2"] + params["b2"]
                         - batch["y"]) ** 2)

    return params, batch, loss_fn


@pytest.fixture
def world_of_four():
    hvd.init(devices=jax.devices()[:N])
    yield
    hvd.shutdown()


def test_four_devices_give_what_one_device_gives_the_whole_batch(
        world_of_four, monkeypatch):
    params, batch, loss_fn = _mlp_problem()
    handed = []
    real_jit = jax.jit

    def recording_jit(fun, **kwargs):
        handed.append(kwargs.get("compiler_options"))
        return real_jit(fun, **kwargs)

    with monkeypatch.context() as patched:
        patched.setattr(dp.jax, "jit", recording_jit)
        step, init = hvd.make_data_parallel_step(
            loss_fn, optax.sgd(0.1, momentum=0.9))
    # The CPU's compiler refuses a TPU option by name: none is handed.
    assert handed == [None]

    optimizer = optax.sgd(0.1, momentum=0.9)
    alone, alone_state = params, optimizer.init(params)
    spread = hvd.broadcast_parameters(params)
    spread_state = hvd.replicate(init(spread))
    sharded = hvd.shard_batch(batch)
    for _ in range(3):
        loss_alone, grads = jax.value_and_grad(loss_fn)(alone, batch)
        updates, alone_state = optimizer.update(grads, alone_state, alone)
        alone = optax.apply_updates(alone, updates)
        spread, spread_state, loss = step(spread, spread_state, sharded)
        np.testing.assert_allclose(float(loss), float(loss_alone),
                                   rtol=1e-5)
    for name in params:
        np.testing.assert_allclose(np.asarray(spread[name]),
                                   np.asarray(alone[name]),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("codec", ["int8", "fp8"])
@pytest.mark.parametrize("entry", ["allreduce_gradients",
                                   "grouped_allreduce"])
def test_a_quantizing_codec_is_still_refused(entry, codec):
    compression = getattr(hvd.Compression, codec)
    tree = {"w": jnp.ones((4,))}
    with pytest.raises(ValueError, match="cannot use"):
        if entry == "allreduce_gradients":
            allreduce_gradients(tree, axis_name=AXIS,
                                compression=compression)
        else:
            spmd.grouped_allreduce([tree["w"]], axis_name=AXIS,
                                   compression=compression)
