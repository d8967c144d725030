"""The program times its own set-up (``common/metrics.py: span``).

A host span is at once an observation of ``hvd_span_seconds{span}``, a
record with a parent in ``hvd.span_records()`` and a
``jax.profiler.TraceAnnotation``; ``hvd.init``, the state-placing half of
the step builders, the broadcasts and ``shard_batch`` open one, and JAX's
own compile-stage events are kept as records from ``hvd.init()`` on
(``common/device.py``).  CPU world, tiny sizes; nothing here is a speed.
"""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu.jax as hvd
from horovod_tpu.common import device, metrics, scopes
from horovod_tpu.models import bert, transformer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_STAGES = (scopes.COMPILE_TRACE, scopes.COMPILE_LOWER,
                  scopes.COMPILE_BACKEND)


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.reset()
    yield
    metrics.reset()


def _named(name):
    return [r for r in metrics.span_records() if r.name == name]


def _count(name):
    """Observations of ``hvd_span_seconds{span=name}``."""
    rows = metrics.snapshot().get("hvd_span_seconds", {}).get("series", ())
    return sum(r["count"] for r in rows if r["labels"] == {"span": name})


def test_nesting_gives_parents_and_self_times():
    with metrics.span(scopes.INIT) as outer:
        time.sleep(0.02)
        with metrics.span(scopes.INIT_PLAN):
            time.sleep(0.03)
        with metrics.span(scopes.INIT_ENGINE):
            time.sleep(0.01)
    with metrics.span(scopes.MESH):
        pass
    plan, engine, init, mesh = metrics.span_records()
    assert [r.name for r in (plan, engine, init, mesh)] == [
        scopes.INIT_PLAN, scopes.INIT_ENGINE, scopes.INIT, scopes.MESH]
    assert plan.parent == engine.parent == init.id == outer._id
    assert init.parent is None and mesh.parent is None
    assert len({r.id for r in (plan, engine, init, mesh)}) == 4
    assert init.start <= plan.start <= plan.end <= engine.start \
        <= engine.end <= init.end
    assert abs(init.start - time.time()) < 60      # the epoch, not a counter
    own = metrics.span_self_seconds(metrics.span_records())
    lengths = {r.id: r.end - r.start for r in (plan, engine, init, mesh)}
    assert own[plan.id] == lengths[plan.id]
    assert own[init.id] == pytest.approx(
        lengths[init.id] - lengths[plan.id] - lengths[engine.id], abs=1e-9)
    assert 0.02 <= own[init.id] < lengths[init.id] - 0.04


def test_self_time_counts_overlapping_children_once():
    """Stage records of nested ``jit``s lie inside one another."""
    with metrics.span(scopes.BUILD_STATE) as outer:
        pass
    rec = metrics.span_records()[0]
    inside = [metrics.SpanRecord(100 + i, outer._id, scopes.COMPILE_TRACE,
                                 rec.start + lo, rec.start + hi, {})
              for i, (lo, hi) in enumerate(((1.0, 4.0), (2.0, 3.0),
                                            (3.5, 5.0), (7.0, 8.0)))]
    rec = rec._replace(end=rec.start + 10.0)
    own = metrics.span_self_seconds([rec] + inside)
    assert own[rec.id] == pytest.approx(10.0 - 4.0 - 1.0)


def test_a_span_closes_and_records_when_its_body_raises():
    with pytest.raises(ZeroDivisionError):
        with metrics.span(scopes.BROADCAST, leaves=3):
            with metrics.span(scopes.SHARD_BATCH):
                1 / 0
    inner, outer = metrics.span_records()
    assert (inner.name, outer.name) == (scopes.SHARD_BATCH, scopes.BROADCAST)
    assert inner.parent == outer.id and outer.attributes == {"leaves": 3}
    assert _count(scopes.BROADCAST) == _count(scopes.SHARD_BATCH) == 1
    # Nothing is left open on this thread.
    with metrics.span(scopes.MESH):
        pass
    assert metrics.span_records()[-1].parent is None


@pytest.mark.parametrize("name", ["hvd.nosuch", scopes.MODEL, "init", ""])
def test_an_undeclared_name_raises(name):
    """As an undeclared series does; a device scope is no host span."""
    with pytest.raises(KeyError):
        metrics.span(name)
    with pytest.raises(KeyError):
        metrics.record_span(name, 0.0, 1.0)
    assert metrics.span_records() == []


def test_the_decorator_form():
    @metrics.span(scopes.MESH, axes=2)
    def make(a, b=1):
        """doc"""
        if a < 0:
            raise ValueError(a)
        return a + b + (make(a - 1) if a else 0)

    assert make.__name__ == "make" and make.__doc__ == "doc"
    assert make(2, b=1) == 3 + 2 + 1
    with pytest.raises(ValueError):
        make(-1)
    records = metrics.span_records()
    assert [r.name for r in records] == [scopes.MESH] * 4
    # Each call is a span of its own: the recursion nests.
    innermost, middle, outermost, raised = records
    assert innermost.parent == middle.id and middle.parent == outermost.id
    assert outermost.parent is None and raised.parent is None
    assert all(r.attributes == {"axes": 2} for r in records)
    assert _count(scopes.MESH) == 4


def test_the_records_are_bounded_and_handed_out_as_a_copy():
    extra = 10
    for i in range(metrics.SPAN_RECORDS_MAX + extra):
        metrics.record_span(scopes.SHARD_BATCH, float(i), float(i) + 0.5)
    records = metrics.span_records()
    assert len(records) == metrics.SPAN_RECORDS_MAX
    assert records[0].start == float(extra)         # the oldest went first
    # The histogram forgets nothing.
    assert _count(scopes.SHARD_BATCH) == metrics.SPAN_RECORDS_MAX + extra
    records.clear()
    assert len(metrics.span_records()) == metrics.SPAN_RECORDS_MAX
    assert hvd.span_records() == metrics.span_records()
    import horovod_tpu
    assert horovod_tpu.span_records is metrics.span_records


def test_spans_of_two_threads_do_not_nest():
    import threading
    inside = threading.Event()
    done = threading.Event()

    def other():
        with metrics.span(scopes.SHARD_BATCH):
            inside.set()
            done.wait(10)

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(10)
    with metrics.span(scopes.MESH):
        pass
    done.set()
    t.join(10)
    assert not t.is_alive()
    assert [r.parent for r in metrics.span_records()] == [None, None]


def test_init_leaves_its_span_with_three_children():
    hvd.shutdown()
    hvd.init()
    try:
        (init,) = _named(scopes.INIT)
        children = [r for r in metrics.span_records() if r.parent == init.id]
        assert [r.name for r in children] == [
            scopes.INIT_DEVICES, scopes.INIT_PLAN, scopes.INIT_ENGINE]
        assert init.parent is None
        for name in (scopes.INIT, scopes.INIT_DEVICES, scopes.INIT_PLAN,
                     scopes.INIT_ENGINE):
            assert _count(name) == 1, name
        own = metrics.span_self_seconds(metrics.span_records())
        assert 0 <= own[init.id] <= init.end - init.start
        # Initialised already: no second span.
        hvd.init()
        assert len(_named(scopes.INIT)) == 1
    finally:
        hvd.shutdown()
    # shutdown() leaves the registry alone (the benchmark reads it after).
    assert len(_named(scopes.INIT)) == 1


def test_a_second_init_does_not_listen_twice(hvd_world):
    """An elastic re-``init`` passes ``place_compile_cache`` again."""
    from jax._src import monitoring
    hvd.shutdown()
    hvd.init()
    device.place_compile_cache()
    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(device._on_compile_stage) == 1
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(3))
    assert [r.attributes["fun_name"] for r in _named(scopes.COMPILE_BACKEND)
            ].count("jit(<lambda>)") == 1


def test_the_stage_events_as_records():
    """JAX's events, handed to the listeners as JAX hands them."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    with metrics.span(scopes.OPTIMIZER_INIT) as outer:
        t0 = time.time()
        device._on_compile_stage(trace, 0.25, fun_name="zeros_like")
        # A jnp function met on the way: inside its caller's, no record.
        device._on_compile_stage(trace, 2e-5, fun_name="add")
        device._on_compile_stage(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 2e-5,
            fun_name="jit(zeros_like)")
        device._on_compile_event("/jax/compilation_cache/cache_hits")
        device._on_compile_stage(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.01)
        device._on_compile_stage(backend, 0.5, fun_name="jit(zeros_like)")
        device._on_compile_stage(backend, 0.5, fun_name="jit(ones_like)")
        # Not a stage: saved time is no interval of this process.
        device._on_compile_stage(
            "/jax/compilation_cache/compile_time_saved_sec", 3.0)
        device._on_compile_event("/jax/compilation_cache/cache_misses")
    records = metrics.span_records()[:-1]
    assert [(r.name, r.attributes) for r in records] == [
        (scopes.COMPILE_TRACE, {"fun_name": "zeros_like"}),
        (scopes.COMPILE_LOWER, {"fun_name": "jit(zeros_like)"}),
        (scopes.COMPILE_CACHE_READ, {}),
        (scopes.COMPILE_BACKEND, {"fun_name": "jit(zeros_like)"}),
        (scopes.COMPILE_BACKEND, {"fun_name": "jit(ones_like)"})]
    assert all(r.parent == outer._id for r in records)
    first = records[0]
    assert first.end - first.start == pytest.approx(0.25)
    assert t0 <= first.end <= time.time()           # it ends as it is heard
    assert metrics.series_sum("hvd_compile_programs_total", cache="hit") == 1
    assert metrics.series_sum("hvd_compile_programs_total", cache="miss") == 1


def _adam_with_a_program_of_its_own():
    """``optax.adam`` whose eager ``init`` also runs a program that no
    process has built: jit keys its cache by the function object, and this
    one is new.  What an earlier test of the same worker has compiled (other
    files build the same tiny states) then cannot empty ``init``'s span."""
    adam = optax.adam(1e-2)
    fresh = jax.jit(lambda x: x + 1)

    def init(params):
        fresh(jnp.zeros(3))
        return adam.init(params)
    return optax.GradientTransformation(init, adam.update)


def _tiny_train_step():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype="float32")
    mesh = hvd.create_mesh((2, 2, 2), ("dp", "sp", "tp"))
    build, shard_batch = transformer.make_train_step(
        cfg, mesh, _adam_with_a_program_of_its_own())
    params_host = transformer.init_params(jax.random.PRNGKey(0), cfg)
    step, params, opt_state = build(params_host)
    tokens = np.random.RandomState(0).randint(
        0, 64, size=(4, 32)).astype(np.int32)
    batch = shard_batch({"tokens": tokens, "targets": tokens})
    return step, params, opt_state, batch


def test_a_train_step_leaves_state_and_compile_records(hvd_world):
    step, params, opt_state, batch = _tiny_train_step()
    (mesh,) = _named(scopes.MESH)
    (built,) = _named(scopes.BUILD_STATE)
    (opt_init,) = _named(scopes.OPTIMIZER_INIT)
    (sharded,) = _named(scopes.SHARD_BATCH)
    assert opt_init.parent == built.id and built.parent is None
    assert built.end - built.start > opt_init.end - opt_init.start
    assert built.start <= opt_init.start <= opt_init.end <= built.end
    assert built.attributes == {"leaves": len(jax.tree.leaves(
        (params, opt_state)))}
    assert mesh.end <= built.start <= built.end <= sharded.start
    # optimizer.init is eager: what it compiles (the program of its own at
    # least; the tiny ones only if this process has not built them before),
    # it compiles under its span, and nothing it compiles lies elsewhere.
    under = [r for r in metrics.span_records()
             if r.parent == opt_init.id and r.name in COMPILE_STAGES]
    assert {scopes.COMPILE_LOWER, scopes.COMPILE_BACKEND} \
        <= {r.name for r in under}
    assert all(r.attributes["fun_name"] for r in under)
    assert all(r.parent == opt_init.id for r in metrics.span_records()
               if r.name in COMPILE_STAGES
               and opt_init.start <= r.start and r.end <= opt_init.end)

    before = len(metrics.span_records())
    params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    first = metrics.span_records()[before:]
    for stage in COMPILE_STAGES:
        own = [r for r in first if r.name == stage
               and "local_step" in r.attributes["fun_name"]]
        assert len(own) == 1, (stage, [r.attributes for r in first])
        assert own[0].parent is None
        assert own[0].end - own[0].start > 0
    # The counter counts the same executables as the backend records.
    backends = _named(scopes.COMPILE_BACKEND)
    assert metrics.series_sum("hvd_compile_programs_total") == len(backends)
    assert _count(scopes.COMPILE_BACKEND) == len(backends)

    # A compiled step that is called again builds nothing: no program is
    # lowered, built or counted.  Handed its own outputs for the first time,
    # jit looks its jaxpr up once more (JAX's trace event round a cache hit,
    # kept as a record only if a loaded host stretches it past a
    # millisecond); after that, no event.
    before = len(metrics.span_records())
    params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    again = metrics.span_records()[before:]
    assert [r.name for r in again] in ([], [scopes.COMPILE_TRACE])
    assert metrics.series_sum("hvd_compile_programs_total") == len(backends)
    before = len(metrics.span_records())
    params, opt_state, loss = step(params, opt_state, batch)
    jax.block_until_ready(loss)
    assert metrics.span_records()[before:] == []


def test_a_finetune_step_leaves_state_records(hvd_world):
    cfg = bert.BertConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                          d_ff=64, max_seq=32, n_classes=3, dtype="float32")
    mesh = Mesh(np.asarray(jax.devices()).reshape((4, 2)), ("dp", "tp"))
    build, shard_batch = bert.make_finetune_step(
        cfg, mesh, optax.adamw(1e-2), objective="mlm")
    step, params, opt_state = build(
        bert.init_params(jax.random.PRNGKey(0), cfg))
    tokens = np.random.RandomState(0).randint(
        0, 64, size=(8, 16)).astype(np.int32)
    shard_batch({"tokens": tokens, "targets": tokens,
                 "mlm_mask": np.ones((8, 16), np.int32)})
    (built,) = _named(scopes.BUILD_STATE)
    (opt_init,) = _named(scopes.OPTIMIZER_INIT)
    assert opt_init.parent == built.id
    assert built.attributes == {"leaves": len(jax.tree.leaves(
        (params, opt_state)))}
    assert len(_named(scopes.SHARD_BATCH)) == 1
    assert _named(scopes.MESH) == []        # a Mesh made by hand has no span


def test_the_data_parallel_builder_and_the_broadcasts(hvd_world):
    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    step, init = hvd.make_data_parallel_step(loss_fn, optax.sgd(0.1))
    # The step is the jit object still; only ``init`` is wrapped.
    assert isinstance(step, type(jax.jit(lambda x: x)))
    params = hvd.broadcast_parameters({"w": jnp.ones((8, 4)),
                                       "b": jnp.zeros((4,))})
    opt_state = hvd.broadcast_optimizer_state(init({"w": params["w"]}))
    assert hvd.broadcast_object({"epoch": 3}) == {"epoch": 3}
    batch = hvd.shard_batch({"x": jnp.ones((16, 8)), "y": jnp.zeros((16, 4))})
    # Placed already: the span is all it costs.
    hvd.shard_batch(batch)
    broadcasts = _named(scopes.BROADCAST)
    assert [r.attributes["leaves"] for r in broadcasts] == [
        2, len(jax.tree.leaves(opt_state)), 1]
    assert len(_named(scopes.OPTIMIZER_INIT)) == 1
    assert len(_named(scopes.SHARD_BATCH)) == 2
    assert all(r.parent is None for r in broadcasts)


def test_a_span_lies_on_the_profilers_clock(tmp_path, hvd_world):
    """Under any profile a span is a host event of the same file as the
    device's: what ``yardstick/trace.py: name_gaps`` names idle gaps by."""
    from yardstick import trace as tr
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with metrics.span(scopes.BUILD_STATE):
            with metrics.span(scopes.OPTIMIZER_INIT):
                jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    finally:
        jax.profiler.stop_trace()
    data = tr.read_xplane(tr.find_xplane(str(tmp_path)))
    spans = {name: (start, end) for name, start, end in tr.host_spans(data)}
    assert scopes.BUILD_STATE in spans and scopes.OPTIMIZER_INIT in spans
    outer, inner = spans[scopes.BUILD_STATE], spans[scopes.OPTIMIZER_INIT]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    (record,) = _named(scopes.BUILD_STATE)
    # Same length on both clocks, to a millisecond.
    assert (outer[1] - outer[0]) * 1e-9 == pytest.approx(
        record.end - record.start, abs=1e-3)


def test_the_import_is_recorded_once_and_needs_no_init():
    """A fresh process: ``hvd.import`` is there before ``hvd.init()``, a
    record and an observation, and a span opened before jax is imported
    does not import it."""
    code = (
        "import sys\n"
        "from horovod_tpu.common import metrics, scopes\n"
        "with metrics.span(scopes.INIT_PLAN):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules\n"
        "import horovod_tpu.jax as hvd\n"
        "names = [r.name for r in hvd.span_records()]\n"
        "assert names == [scopes.INIT_PLAN, scopes.IMPORT], names\n"
        "r = hvd.span_records()[1]\n"
        "assert r.parent is None and 0 < r.end - r.start < 600\n"
        "rows = hvd.metrics_snapshot()['hvd_span_seconds']['series']\n"
        "assert sorted(x['labels']['span'] for x in rows) == sorted(names)\n"
        "print('ok')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
