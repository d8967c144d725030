"""What this process runs on, decided in one place.

* ``on_tpu`` — the one answer to "compiled Pallas kernel or the
  interpreter": compiled exactly when the backend is ``tpu``,
  interpreted exactly when it is ``cpu`` (the test world), an error for
  anything else.  A run that was meant for the chip and landed
  elsewhere must fail here, not finish in interpret mode.
* ``place_compile_cache`` — where JAX keeps compiled programs between
  processes and runs; from there on the process also keeps JAX's own
  compile-stage events as host spans (``_watch_compiles``).
"""

from __future__ import annotations

import os
import threading
import time

from . import metrics, scopes

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def on_tpu() -> bool:
    import jax
    platform = jax.default_backend()
    if platform == "tpu":
        return True
    if platform == "cpu":
        return False
    raise RuntimeError(
        "horovod_tpu runs on the 'tpu' backend, or on 'cpu' for tests "
        "(Pallas kernels interpreted); jax.default_backend() is %r"
        % platform)


# JAX's duration events (jax 0.9.0: ``jax/_src/dispatch.py``,
# ``compiler.py``) and the span each is kept as.
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": scopes.COMPILE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": scopes.COMPILE_LOWER,
    "/jax/core/compile/backend_compile_duration": scopes.COMPILE_BACKEND,
    "/jax/compilation_cache/cache_retrieval_time_sec":
        scopes.COMPILE_CACHE_READ,
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# Every jnp function met while a step is traced raises a trace event of its
# own, tens of microseconds each and a thousand of them in a BERT step, all
# inside their caller's.  Kept, they would push set-up's records out of the
# bounded list; a trace shorter than this leaves no span.
_TRACE_MIN_SECONDS = 1e-3

_watching = False               # the listeners are registered, once a process
_compiling = threading.local()  # .hit: this thread's program came from the cache


def _on_compile_stage(event, seconds, **attributes):
    """One of JAX's stage durations, as it ends, on the thread that
    compiles: a span record that ends now (``metrics.record_span``: the
    span open on this thread is its parent, ``fun_name`` its attribute).
    ``backend_compile_duration`` closes a program, built or loaded, so it
    is also where the program is counted."""
    name = _COMPILE_STAGES.get(event)
    if name is None or (name == scopes.COMPILE_TRACE
                        and seconds < _TRACE_MIN_SECONDS):
        return
    now = time.time()
    metrics.record_span(name, now - seconds, now, **attributes)
    if name == scopes.COMPILE_BACKEND:
        hit = getattr(_compiling, "hit", False)
        _compiling.hit = False
        metrics.counter("hvd_compile_programs_total",
                        cache="hit" if hit else "miss").inc()


def _on_compile_event(event, **_attributes):
    # Fires inside the program's backend stage, before its duration.
    # JAX's ``cache_misses`` is no use for the other label: it fires only
    # for a program whose entry gets written (compiled for longer than
    # ``jax_persistent_cache_min_compile_time_secs``).
    if event == _CACHE_HIT:
        _compiling.hit = True


def _watch_compiles():
    """Once a process (an elastic re-``init`` passes here again): the
    listeners above.  They run only when JAX compiles; a compiled step
    that is called again raises no event."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_compile_stage)
    jax.monitoring.register_event_listener(_on_compile_event)


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a directory and return
    it.  ``JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it
    itself and nothing is set in code.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of what makes a later process find the entry.  Every process of a
    job (the engine's executor thread, the launcher's workers, the
    smoke's legs) passes through ``hvd.init()`` and so lands here.

    Wherever it lives, an entry is keyed with its operations' names and
    source lines: JAX's default strips them from the key, and a step
    would then be handed an executable compiled before it named its
    parts (``common/scopes.py``), whose optimized HLO, which is where a
    profile's operations find their scope, names nothing.

    Being the one place every process passes before it compiles
    anything, it is also where the process starts to keep JAX's compile
    stages as host spans (``_watch_compiles``)."""
    import jax
    _watch_compiles()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
