"""The state-space scan's two Pallas kernels (``ops/ssd_kernels.py``, run
interpreted on the CPU) against the token-by-token recurrence kept with the
benchmark (``yardstick/builders/nemotron_h.py: ssm_recurrence``) and
against the XLA form, at the smallest shapes the kernels take: heads of 64
in groups that fill a lane tile, a state of 128, chunks of 128."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import metrics, scopes
from horovod_tpu.models import state_space
from horovod_tpu.models.state_space import (SsmConfig, init_ssm_params,
                                            ssd_chunked, ssd_chunked_xla,
                                            state_space_block)
from horovod_tpu.ops import ssd_kernels
from tests.test_hybrid_decoder import worst
from tests.test_scopes import _pallas_calls
from yardstick.builders import nemotron_h as reference

NAMES = ("x", "dt", "a", "b", "c", "d_skip")


def scan_inputs(batch, seq, heads, groups, dtype=jnp.float32, head=64,
                state=128):
    """``dt`` in the layers' own range (``SsmConfig.dt_min`` to twice
    ``dt_max``), ``A`` in [-16, -1]."""
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    return (jax.random.normal(ks[0], (batch, seq, heads, head)).astype(dtype),
            jnp.exp(jax.random.uniform(ks[1], (batch, seq, heads),
                                       minval=np.log(1e-3),
                                       maxval=np.log(0.2))),
            -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (batch, seq, groups, state))
            .astype(dtype),
            jax.random.normal(ks[4], (batch, seq, groups, state))
            .astype(dtype),
            jax.random.normal(ks[5], (heads,))), \
        jax.random.normal(ks[6], (batch, seq, heads, head))


def recurrence(x, dt, a, b, c, d):
    """The reference over a batch, float32 whatever the inputs are."""
    x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    return jax.vmap(reference.ssm_recurrence,
                    in_axes=(0, 0, None, 0, 0, None))(x, dt, a, b, c, d)


def values_and_grads(fn, args, weight):
    y, vjp = jax.vjp(fn, *args)
    return y, dict(zip(NAMES, vjp(weight.astype(y.dtype))))


# Two chunks a sequence at the least, so the carried state and its gradient
# take part; a batch of 2; two lane tiles a group (four heads); one head a
# lane tile's half with a chunk of 256; a head a lane tile and four heads a
# lane tile.
@pytest.mark.parametrize(
    "batch, seq, heads, head, groups, chunk, dtype, tol", [
        (1, 256, 4, 64, 2, 128, jnp.float32, 2e-5),
        (2, 256, 8, 64, 2, 128, jnp.float32, 2e-5),
        (1, 512, 2, 64, 1, 256, jnp.float32, 2e-5),
        (1, 256, 2, 128, 2, 128, jnp.float32, 2e-5),
        (1, 256, 4, 32, 1, 128, jnp.float32, 2e-5),
        (1, 256, 4, 64, 2, 128, jnp.bfloat16, 1e-2),
        (2, 384, 4, 64, 1, 128, jnp.bfloat16, 1e-2)])
def test_the_kernels_are_the_recurrence_values_and_gradients(
        batch, seq, heads, head, groups, chunk, dtype, tol):
    """float32: what is left is the order of the sums.  bfloat16
    activations: the products' operands are rounded as the XLA form rounds
    them (``L . C B^T``, ``dt . X``, the states), so the two forms stand
    equally far from the float32 recurrence and close to each other."""
    args, weight = scan_inputs(batch, seq, heads, groups, dtype, head)
    assert ssd_kernels.takes(head, heads // groups, 128, chunk)
    got, grads = jax.jit(lambda *a: values_and_grads(
        lambda *z: ssd_chunked(*z, chunk), a, weight))(*args)
    want, ref_grads = jax.jit(lambda *a: values_and_grads(
        recurrence, a, weight))(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    assert worst(got, want) < tol
    for name in NAMES:
        assert grads[name].dtype == ref_grads[name].dtype, name
        assert grads[name].shape == ref_grads[name].shape, name
    as32 = jax.tree.map(lambda v: v.astype(jnp.float32), (grads, ref_grads))
    # A's gradient sums differences of large terms over every step
    assert worst(*as32) < (tol if dtype == jnp.bfloat16 else 4e-4)
    if dtype == jnp.bfloat16:
        xla, xla_grads = jax.jit(lambda *a: values_and_grads(
            lambda *z: ssd_chunked_xla(*z, chunk), a, weight))(*args)
        assert worst(got, xla) < tol / 2
        assert worst(as32[0], jax.tree.map(
            lambda v: v.astype(jnp.float32), xla_grads)) < tol


def test_a_strong_decay_neither_overflows_nor_loses_the_near_steps():
    """``dt A`` of -40 a step against the kernel form: ``exp(-cum_s)``
    alone would overflow within three steps, and a mask put on after the
    exponential would multiply ``inf`` by 0; every exponent is a
    difference taken and masked first, in both passes."""
    (x, dt, _, b, c, d), weight = scan_inputs(2, 256, 4, 2)
    a = jnp.full((4,), -40.0)
    one = jnp.ones_like(dt)
    got = ssd_chunked(x, one, a, b, c, d, 128)
    assert bool(jnp.isfinite(got).all())
    # the state is gone after a step: y_t = x_t (B_t . C_t) + D x_t
    bc = jnp.repeat(jnp.sum(b * c, -1), 2, axis=2)[..., None]
    want = x * bc + d[:, None] * x
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())
    grads = jax.grad(lambda *z: (ssd_chunked(*z, 128) * weight).sum(),
                     tuple(range(6)))(x, one, a, b, c, d)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    want = jax.grad(lambda *z: (ssd_chunked_xla(*z, 128) * weight).sum(),
                    tuple(range(6)))(x, one, a, b, c, d)
    grads, want = dict(zip(NAMES, grads)), dict(zip(NAMES, want))
    # A's gradient is e^-40 of the others': what the kernel leaves there
    # is the rounding of sums that cancel, measured against dt's gradient
    dt_scale = float(jnp.abs(want["dt"]).max())
    assert float(jnp.abs(want["a"]).max()) < 1e-12 * dt_scale
    assert float(jnp.abs(grads.pop("a")).max()) < 1e-4 * dt_scale
    del want["a"]
    assert worst(grads, want) < 1e-5


def test_the_kernels_run_in_a_shard_map_that_checks_what_varies():
    """As a step builder runs them: the tokens split over ``dp``, ``A`` and
    ``D`` whole on every shard, ``check_vma=True``; the parameters'
    gradients are summed over the shards by the map, as autodiff of the
    XLA form's are."""
    from jax.sharding import Mesh, PartitionSpec as P
    args, weight = scan_inputs(2, 256, 4, 2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    every = tuple(range(6))
    specs = (P("dp"), P("dp"), P(), P("dp"), P("dp"), P())

    def grads(form):
        def local(weight, *a):
            return jax.grad(lambda *z: jax.lax.psum(
                (form(*z, 128) * weight).sum(), "dp"), every)(*a)
        return jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(P("dp"),) + specs, out_specs=specs,
            check_vma=True))(weight, *args)

    assert worst(grads(ssd_chunked), grads(ssd_chunked_xla)) < 4e-4


def test_a_recomputed_layer_trains_alike_under_both_forms(monkeypatch):
    """``make_train_step`` over a two-chip ``dp`` mesh, every layer
    recomputed: a state-space block whose shapes take the kernels, then the
    same block sent down the XLA form."""
    import optax
    from jax.sharding import Mesh
    from horovod_tpu.models import transformer

    def losses():
        cfg = transformer.TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, max_seq=256, dtype="float32", remat=True,
            layer_pattern=(("state_space", None), ("attention", "dense")),
            state_space=SsmConfig(n_heads=4, head_size=64, n_groups=2,
                                  state_size=128, chunk=128))
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                    ("dp", "sp", "tp"))
        build, shard_batch = transformer.make_train_step(cfg, mesh,
                                                         optax.adam(1e-2))
        step, params, opt_state = build(
            transformer.init_params(jax.random.PRNGKey(0), cfg))
        tokens = np.random.RandomState(0).randint(
            0, 64, size=(2, 256)).astype(np.int32)
        batch = shard_batch({"tokens": tokens,
                             "targets": np.roll(tokens, -1, axis=1)})
        out = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, batch)
            out.append(float(loss))
        return out

    metrics.reset()
    kernel = losses()
    assert _scan_calls() == {"kernel": 1}
    monkeypatch.setattr(ssd_kernels, "takes", lambda *shape: False)
    xla = losses()
    assert _scan_calls() == {"kernel": 1, "xla": 1}
    assert kernel[-1] < kernel[0] - 1.0
    np.testing.assert_allclose(kernel, xla, rtol=1e-5)


CELL_SHAPE = dict(head_size=64, per_group=8, state_size=128, chunk=128)


@pytest.mark.parametrize("shape, taken", [
    (CELL_SHAPE, True),
    (dict(CELL_SHAPE, chunk=256), True),
    (dict(CELL_SHAPE, head_size=128, per_group=1), True),
    (dict(CELL_SHAPE, per_group=16), True),
    (dict(CELL_SHAPE, per_group=32), False),     # wider than VMEM holds
    (dict(CELL_SHAPE, per_group=1), False),      # a group is half a tile
    (dict(CELL_SHAPE, head_size=16), False),
    (dict(CELL_SHAPE, state_size=64), False),
    (dict(CELL_SHAPE, chunk=64), False),
    (dict(CELL_SHAPE, chunk=512), False),
    (dict(head_size=16, per_group=2, state_size=16, chunk=16), False),
    (dict(head_size=16, per_group=2, state_size=16, chunk=32), False)])
def test_shapes_choose_the_form(shape, taken):
    assert ssd_kernels.takes(**shape) is taken


def _scan_calls():
    series = metrics.metrics_snapshot().get(
        "hvd_ssd_scan_calls_total", {}).get("series", ())
    return {row["labels"]["form"]: row["value"] for row in series}


def _pallas_names(fn, *args):
    return {name for name in (scopes.kernel_name(scopes.SSD_FWD),
                              scopes.kernel_name(scopes.SSD_BWD))
            if name in str(jax.make_jaxpr(fn)(*args))}


def test_the_counter_says_which_form_a_traced_scan_took():
    """The cell's shape (2 x 8192 tokens, 64 heads of 64 in 8 groups, state
    128, chunk 128, bfloat16), traced and not run, takes the kernels; the
    tiny configurations' shape takes the XLA form, and there ``ssd_chunked``
    IS ``ssd_chunked_xla``."""
    def shapes(batch, seq, heads, head, groups, state, dtype):
        s = jax.ShapeDtypeStruct
        return (s((batch, seq, heads, head), dtype),
                s((batch, seq, heads), jnp.float32), s((heads,), jnp.float32),
                s((batch, seq, groups, state), dtype),
                s((batch, seq, groups, state), dtype),
                s((heads,), jnp.float32))

    def both_passes(chunk):
        return jax.grad(lambda *a: ssd_chunked(*a, chunk).sum(),
                        tuple(range(6)))

    metrics.reset()
    cell = shapes(2, 8192, 64, 64, 8, 128, jnp.bfloat16)
    assert _pallas_names(both_passes(128), *cell) \
        == {"hvd_ssd_fwd", "hvd_ssd_bwd"}
    assert _scan_calls() == {"kernel": 1}
    tiny = shapes(2, 96, 4, 16, 2, 16, jnp.float32)
    assert _pallas_names(both_passes(16), *tiny) == set()
    assert _scan_calls() == {"kernel": 1, "xla": 1}
    args, _ = scan_inputs(2, 96, 4, 2, head=16, state=16)
    for chunk in (16, 32):
        assert bool((ssd_chunked(*args, chunk)
                     == ssd_chunked_xla(*args, chunk)).all())
    assert _scan_calls() == {"kernel": 1, "xla": 3}


def test_the_kernels_sit_under_the_core_with_their_names():
    """A mixer whose shapes take the kernels, both passes: each
    ``pallas_call`` under ``hvd.state_space/hvd.ssd_core/<its scope>`` with
    its ``name=``, the running sums under the core and outside the kernels'
    scopes, and nothing of the scan kept across a recomputation."""
    cfg = SsmConfig(n_heads=4, head_size=64, n_groups=2, state_size=128,
                    chunk=128)
    lp = jax.tree.map(lambda v: v[0], init_ssm_params(
        jax.random.PRNGKey(0), 32, cfg, 1, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, lp: state_space_block(x, lp, cfg).sum(), (0, 1)))(x, lp)
    calls = _pallas_calls(jaxpr.jaxpr, [])
    assert sorted(name for _, name in calls) == ["hvd_ssd_bwd", "hvd_ssd_fwd"]
    for stack, name in calls:
        scope = name.replace("hvd_", "hvd.")
        # jvp(hvd.state_space)/hvd.ssd_core/hvd.ssd_fwd/hvd_ssd_fwd
        assert scopes.STATE_SPACE + ")" in stack.split("/")[0], stack
        assert stack.endswith("/".join([scopes.SSD_CORE, scope, name])), stack
    text = str(jax.jit(lambda x, lp: state_space_block(x, lp, cfg)).lower(
        x, lp).as_text(debug_info=True))
    sums = [ln for ln in text.splitlines() if "(cumsum)" in ln]
    assert sums and all(scopes.SSD_CORE in ln and scopes.SSD_FWD not in ln
                        for ln in sums)
    assert state_space.SAVED == ()
