"""What this process runs on, decided in one place.

* ``on_tpu`` — the one answer to "compiled Pallas kernel or the
  interpreter": compiled exactly when the backend is ``tpu``,
  interpreted exactly when it is ``cpu`` (the test world), an error for
  anything else.  A run that was meant for the chip and landed
  elsewhere must fail here, not finish in interpret mode.
* ``place_compile_cache`` — where JAX keeps compiled programs between
  processes and runs.
"""

from __future__ import annotations

import os

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
    profile's operations find their scope, names nothing."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
