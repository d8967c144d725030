"""The names the program gives its own parts: the compiled step's on the
device (``jax.named_scope``), and its own work's on the host
(``metrics.span``).

A ``jax.named_scope`` is metadata written while a step is traced: every
operation traced under it carries the scope in its ``op_name``
(``jit(step)/jvp(hvd.model)/dot_general``), the optimized HLO of the
loaded executable keeps it (a fusion carries its root's), and the
profiler's trace viewer shows it.  Nothing runs on the hot path.  This is
the whole vocabulary; ``docs/observability.md`` ("Scopes in the compiled
step") says what falls under each and which benchmark metric reads it.

Phases, opened by the step builders (``jax/data_parallel.py``,
``models/bert.py``, ``models/transformer.py``):

* ``MODEL``      round the loss inside ``jax.value_and_grad``: the forward
                 pass is ``jvp(hvd.model)``, the backward pass
                 ``transpose(jvp(hvd.model))``
* ``OPTIMIZER``  round ``optimizer.update`` and ``optax.apply_updates``
* ``EXCHANGE``   the in-program gradient all-reduces, one ``psum`` a
                 leaf, and what scales or casts the gradients round them
                 (``allreduce_gradients``; nothing is packed, but for the
                 hierarchical form); it sits inside ``OPTIMIZER`` and the
                 inner scope wins

Blocks inside the model, both passes: ``ATTENTION`` (softmax attention
with its projections), ``HEAD`` (logits and cross entropy), ``DENSE_FFN``
(a dense SwiGLU feed-forward whole), ``LINEAR_ATTENTION`` (a delta-rule
mixer whole; ``KDA_CORE`` inside it is the chunked recurrence alone,
kernels or XLA form, of a layer with a decay for every key channel, and
``GATED_DELTA_CORE`` the same of a layer with one decay a head),
``STATE_SPACE`` (a Mamba-2 mixer whole: projections, convolution, scan,
gated norm; ``SSD_CORE`` inside it is the chunked state-space scan alone,
kernels or XLA form), ``MOE`` (an
expert layer whole; inside it ``ROUTER`` is the scores, the choice, the sort
of the pairs by expert, the blocks' indices and weights and, under
``ROUTER_ROWS``, the row movement alone: each block's gathers of its
tokens' rows and the write of its rows by the ``hvd_moe_combine`` kernel;
``EXPERTS`` the routed experts' matrix products alone, ``SHARED_EXPERT``
the expert every token takes).  Kernels, one
``pallas_call`` each: ``FLASH_FWD``, ``FLASH_DQ``, ``FLASH_DKV``,
``FLASH_BWD_ONEPASS`` (the one backward kernel of a full call: dq, dk and
dv; ``FLASH_DQ`` and ``FLASH_DKV`` are the two it replaces wherever dq of a
head fits in VMEM), and ``FLASH_WINDOW_FWD``, ``FLASH_WINDOW_DQ``,
``FLASH_WINDOW_DKV`` for the same under a window (a banded call's one
backward kernel sits under ``FLASH_WINDOW_DKV``; a sliding layer's
whole block is ``WINDOW_ATTENTION``, inside ``ATTENTION``, and a
latent-attention layer's ``LATENT_ATTENTION``, inside ``ATTENTION`` too: both
projections of the latent, its norm, the rotary turns, the flash kernels,
``wo``); ``KDA_FWD`` and ``KDA_BWD`` (the delta rule's two, inside
``KDA_CORE`` or ``GATED_DELTA_CORE``); ``SSD_FWD`` and ``SSD_BWD`` (the
state-space scan's two, inside ``SSD_CORE``).  A learned-sparse-attention
layer's whole block is ``SPARSE_ATTENTION`` (inside ``ATTENTION``): inside
it ``INDEXER`` is the lightning indexer's projections, scores, choice and
loss, ``INDEXER_TOPK`` (inside ``INDEXER``) the kernel that chooses every
query's keys, and ``SPARSE_FLASH_FWD`` and ``SPARSE_FLASH_BWD`` the attention
over the chosen keys (``ops/sparse_kernels.py``; the indexer loss's
gradient is computed inside the two backward kernels, so it is
``SPARSE_FLASH_BWD``'s time).  ``kernel_name`` gives the
same words as the ``name=`` of the call (``hvd_flash_fwd``), which is
what the trace viewer prints for a Mosaic kernel.

Host spans (``common/metrics.py: span``; ``host_spans`` is the whole
list, and a name outside it raises there).  They time the program's
set-up, nothing inside a step; ``docs/observability.md`` ("Host spans")
has the table:

* ``IMPORT``       the package's first line to the last of
                   ``horovod_tpu/jax/__init__.py``, once a process
* ``INIT``         the body of ``hvd.init()`` that runs when not yet
                   initialised; inside it ``INIT_DEVICES`` (the runtime
                   reaching its devices: ``jax.devices()``, or
                   ``jax.distributed`` in a multihost world),
                   ``INIT_PLAN`` (the collective-plan cache) and
                   ``INIT_ENGINE`` (the collective engine, or the native
                   core and the multihost engine)
* ``MESH``         ``jax/mesh.py: create_mesh``
* ``BUILD_STATE``  a model's ``build(params_host)``: parameters and
                   optimizer state placed on the mesh; inside it
                   ``OPTIMIZER_INIT`` round ``optimizer.init`` (also
                   what ``make_data_parallel_step`` hands back)
* ``BROADCAST``    ``broadcast_parameters`` / ``_optimizer_state`` /
                   ``_object``
* ``SHARD_BATCH``  the three ``shard_batch``s
* ``COMPILE_TRACE``, ``COMPILE_LOWER``, ``COMPILE_BACKEND``,
  ``COMPILE_CACHE_READ``  JAX's own stage events kept as spans
                   (``common/device.py: place_compile_cache``): tracing
                   to a jaxpr, lowering to a module, building or loading
                   the executable (the cache's key, read and load
                   included), and inside that the read from the
                   persistent cache
"""

from __future__ import annotations

MODEL = "hvd.model"
OPTIMIZER = "hvd.optimizer"
EXCHANGE = "hvd.exchange"
ATTENTION = "hvd.attention"
HEAD = "hvd.head"
LINEAR_ATTENTION = "hvd.linear_attention"
KDA_CORE = "hvd.kda_core"
GATED_DELTA_CORE = "hvd.gated_delta_core"
DENSE_FFN = "hvd.dense_ffn"
STATE_SPACE = "hvd.state_space"
SSD_CORE = "hvd.ssd_core"
MOE = "hvd.moe"
ROUTER = "hvd.router"
ROUTER_ROWS = "hvd.router_rows"
EXPERTS = "hvd.experts"
SHARED_EXPERT = "hvd.shared_expert"
FLASH_FWD = "hvd.flash_fwd"
FLASH_DQ = "hvd.flash_dq"
FLASH_DKV = "hvd.flash_dkv"
FLASH_BWD_ONEPASS = "hvd.flash_bwd_onepass"
WINDOW_ATTENTION = "hvd.window_attention"
LATENT_ATTENTION = "hvd.latent_attention"
FLASH_WINDOW_FWD = "hvd.flash_window_fwd"
FLASH_WINDOW_DQ = "hvd.flash_window_dq"
FLASH_WINDOW_DKV = "hvd.flash_window_dkv"
KDA_FWD = "hvd.kda_fwd"
KDA_BWD = "hvd.kda_bwd"
SSD_FWD = "hvd.ssd_fwd"
SSD_BWD = "hvd.ssd_bwd"
SPARSE_ATTENTION = "hvd.sparse_attention"
INDEXER = "hvd.indexer"
INDEXER_TOPK = "hvd.indexer_topk"
SPARSE_FLASH_FWD = "hvd.sparse_flash_fwd"
SPARSE_FLASH_BWD = "hvd.sparse_flash_bwd"

IMPORT = "hvd.import"
INIT = "hvd.init"
INIT_DEVICES = "hvd.init_devices"
INIT_PLAN = "hvd.init_plan"
INIT_ENGINE = "hvd.init_engine"
MESH = "hvd.mesh"
BUILD_STATE = "hvd.build_state"
OPTIMIZER_INIT = "hvd.optimizer_init"
BROADCAST = "hvd.broadcast"
SHARD_BATCH = "hvd.shard_batch"
COMPILE_TRACE = "hvd.compile_trace"
COMPILE_LOWER = "hvd.compile_lower"
COMPILE_BACKEND = "hvd.compile_backend"
COMPILE_CACHE_READ = "hvd.compile_cache_read"

# Lower case: every upper-case name of this module is one scope's or one
# span's string, and tests walk them.
host_spans = frozenset((
    IMPORT, INIT, INIT_DEVICES, INIT_PLAN, INIT_ENGINE, MESH, BUILD_STATE,
    OPTIMIZER_INIT, BROADCAST, SHARD_BATCH, COMPILE_TRACE, COMPILE_LOWER,
    COMPILE_BACKEND, COMPILE_CACHE_READ))


def kernel_name(scope: str) -> str:
    """A kernel scope as a ``pallas_call``'s ``name=``: Mosaic takes
    letters, digits and ``_``."""
    return scope.replace(".", "_")
