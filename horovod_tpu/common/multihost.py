"""Multihost bootstrap: join every worker process into one global JAX
runtime.

TPU-native counterpart of the reference's MPI bootstrap
(``horovod/common/mpi/mpi_context.cc`` ``MPI_Init`` rank assignment,
SURVEY.md §2.6): on TPU pods the coordination service behind
``jax.distributed.initialize`` plays MPI's role — it wires one process
per host into a runtime where ``jax.devices()`` spans the pod and XLA
collectives ride ICI/DCN.  The coordinator address travels the same way
Gloo's rendezvous does in the reference: rank 0 advertises it through
the launcher's HTTP KV store.

On the CPU test world (``JAX_PLATFORMS=cpu`` with
``--xla_force_host_platform_device_count=N``) the same code path forms
an n-process × N-device global mesh with gloo carrying the cross-process
collectives — the Gloo-on-localhost test strategy of the reference
(SURVEY.md §4) applied to the payload plane.
"""

from __future__ import annotations

import logging
import os
import socket
import threading
from typing import Optional

from . import faultline

LOG = logging.getLogger("horovod_tpu")


def _is_elastic_world() -> bool:
    """True for workers launched by the elastic driver (it exports
    ``HOROVOD_ELASTIC=1``; the driver address doubles as the marker for
    programmatic launches)."""
    return (os.environ.get("HOROVOD_ELASTIC") == "1"
            or bool(os.environ.get("HOROVOD_ELASTIC_DRIVER_ADDR")))


def _free_port() -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def resolve_coordinator(config, rank: int, size: int) -> str:
    """Coordinator address: explicit env/config, the rendezvous KV, or a
    deterministic localhost port for single-host worlds."""
    if config.coordinator_addr:
        return config.coordinator_addr
    if config.rendezvous_addr:
        from ..runner.http_client import RendezvousClient
        client = RendezvousClient(config.rendezvous_addr,
                                  secret=config.secret_key)
        # The KV outlives elastic world changes: version the key by
        # the world round (driver epoch), or a re-rendezvoused worker
        # reads the PREVIOUS world's dead coordinator address and the
        # new jax runtime never forms.
        key = ("jax_coordinator:%s"
               % os.environ.get("HOROVOD_ELASTIC_EPOCH", "0"))
        if rank == 0:
            host = os.environ.get("HOROVOD_HOSTNAME", "127.0.0.1")
            addr = "%s:%d" % (host, _free_port())
            client.put(key, addr)
            return addr
        return client.get_blocking(key, timeout=120.0)
    # Single-host default: a port derived from the launcher's port base,
    # clear of the tcp-core range [base, base+size).
    base = int(os.environ.get("HOROVOD_PORT_BASE", "29600"))
    return "127.0.0.1:%d" % (base + size + 101)


def init_jax_distributed(config, rank: int, size: int):
    """Join the global JAX runtime (idempotent per process)."""
    import jax

    if getattr(init_jax_distributed, "_done", False):
        return
    # CPU test world: cross-process collectives need the gloo
    # implementation; on TPU the flag only affects the auxiliary CPU
    # backend, so gate on the configured platform.
    platforms = (os.environ.get("JAX_PLATFORMS", "")
                 or str(jax.config.jax_platforms or ""))
    if "cpu" in platforms.split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # Elastic survival: without this, the coordination service's error
    # propagation hard-terminates every healthy process the moment a
    # member dies (absl FATAL in the client) — recovery from member
    # death is impossible.  With it, survivors keep running; a wedged
    # collective is the execution watchdog's job
    # (HOROVOD_DEVICE_EXEC_TIMEOUT_SECONDS), and the elastic driver
    # re-forms the world.
    #
    # Scoped to ELASTIC worlds only: recoverability also removes the
    # runtime's synchronized shutdown barrier, so in a static world the
    # first rank to exit after jax.distributed.shutdown() FATALed the
    # survivors mid-teardown (the r6 MULTICHIP RED).  Static worlds
    # keep the runtime's exit propagation — a member death should kill
    # the world there, loudly and everywhere; elastic worlds get
    # survival plus the explicit teardown barrier below.
    if _is_elastic_world():
        jax.config.update("jax_enable_recoverability", True)
    coordinator = resolve_coordinator(config, rank, size)
    LOG.info("multihost: joining jax.distributed at %s as %d/%d",
             coordinator, rank, size)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=size, process_id=rank)
    init_jax_distributed._done = True
    # Verify the world actually formed.  A backend plugin (or any JAX
    # computation before hvd.init()) can pre-initialize the runtime, in
    # which case distributed init silently does not take effect and
    # every rank would train ALONE while believing it is rank r of N —
    # the worst possible failure mode.  Fail loudly instead.
    got = jax.process_count()
    if size > 1 and got != size:
        raise RuntimeError(
            "multihost init failed: jax.process_count()=%d but the "
            "world has %d ranks. The JAX runtime was initialized "
            "before hvd.init() could join the global world (a platform "
            "plugin or an earlier JAX computation created the backend "
            "first). Call hvd.init() before ANY JAX computation and "
            "disable backend plugins that pre-initialize the runtime."
            % (got, size))


def _teardown_barrier() -> bool:
    """Synchronized teardown: every member reaches this coordination-
    service barrier before ANY member starts ``jax.distributed.
    shutdown()`` — the reference's exit-propagation discipline (no rank
    exits the world while a peer is still inside it).  Bounded: a dead
    member must not hang teardown, so the barrier times out
    (``HOROVOD_SHUTDOWN_BARRIER_TIMEOUT`` seconds; elastic worlds
    default shorter — a broken world is torn down on every
    re-rendezvous and must not serialize recovery on barrier waits).

    Returns True when the world is SYNCHRONIZED for teardown (every
    member at the barrier, or no barrier applicable) and False when a
    member failed to show — the caller must then ABANDON the runtime
    instead of disconnecting from it (see shutdown_jax_distributed).
    """
    default = "5" if _is_elastic_world() else "30"
    try:
        timeout_s = float(os.environ.get(
            "HOROVOD_SHUTDOWN_BARRIER_TIMEOUT", default))
    except ValueError:
        timeout_s = float(default)
    if timeout_s <= 0:
        return True  # barrier disabled: legacy direct-shutdown path
    try:
        from jax._src import distributed as _dist
        client = getattr(_dist.global_state, "client", None)
        if client is None:
            return True
        # Version the barrier id by the elastic epoch: coordination-
        # service barriers are one-shot per id, and an in-process
        # rejoin tears worlds down repeatedly.
        barrier_id = ("hvd_tpu_shutdown:%s"
                      % os.environ.get("HOROVOD_ELASTIC_EPOCH", "0"))
        client.wait_at_barrier(barrier_id, int(timeout_s * 1000))
        return True
    except (ImportError, AttributeError):
        # jax without the private distributed module / wait_at_barrier:
        # no barrier to fail means no broken-world evidence — take the
        # legacy direct-shutdown path, never the abandon path.
        return True
    except Exception as exc:  # noqa: BLE001 - dead/wedged member
        LOG.warning("teardown barrier did not complete (%s); a member "
                    "is dead or wedged — abandoning the distributed "
                    "runtime instead of disconnecting", exc)
        return False


def _wait_for_members_to_leave():
    """The process that holds the coordination service (rank 0) leaves
    last.  A member whose disconnect finds the service gone is killed by
    the runtime (LOG(FATAL) in the client: "Failed to disconnect from
    coordination service"), and a recoverable world has no shutdown
    barrier of the runtime's own to hold the service back: every member
    is past the teardown barrier here, but on a loaded host they run
    seconds apart, and the elastic driver then blacklists the host of a
    healthy worker.  ``get_live_nodes`` returns once every other task has
    disconnected or is dead; a member killed between the barrier and its
    disconnect is only found dead by its heartbeat (100 s), so the wait
    is bounded at 30 s."""
    import jax
    from jax._src import distributed as _dist
    gs = _dist.global_state
    if (gs.service is None or gs.client is None
            or not jax.config.jax_enable_recoverability):
        # Without recoverability the runtime's shutdown barrier already
        # keeps the service up until every member has called shutdown
        # (and asking for the live tasks would wait on it forever).
        return

    def ask():
        try:
            gs.client.get_live_nodes(list(range(gs.num_processes)))
        except Exception as exc:  # noqa: BLE001 - best-effort teardown
            LOG.debug("waiting for the members to leave: %s", exc)

    waiter = threading.Thread(target=ask, daemon=True,
                              name="hvd-teardown-wait")
    waiter.start()
    waiter.join(30.0)
    if waiter.is_alive():
        LOG.warning("a member has not left the distributed runtime 30 s "
                    "after the teardown barrier; stopping the "
                    "coordination service under it")


# Abandoned runtime objects, kept alive deliberately: letting the
# client/service of a BROKEN world be destroyed (or calling their
# shutdown) runs the coordination-service disconnect, and a disconnect
# with a dead member is a LOG(FATAL) in the runtime client
# (xla pjrt distributed client.h "Terminating process...") — the exact
# survivor-killed-mid-teardown failure the barrier exists to prevent.
# Growth is bounded by the number of in-process world re-formations.
_ABANDONED_RUNTIMES: list = []


def _abandon_jax_distributed():
    """Drop jax's global distributed state WITHOUT the disconnect RPC
    so a later ``jax.distributed.initialize`` (elastic rejoin, new
    epoch, new coordinator port) can form a fresh world.

    The abandoned objects are made IMMORTAL (an extra C-level
    reference): their destructors run the same disconnect/shutdown
    paths we are avoiding, and interpreter finalization would
    otherwise trigger them after gRPC's own teardown — observed as a
    LOG(FATAL) that turns a cleanly-finished worker into rc=-6 at the
    last instant.  A leaked client/service pair per in-process world
    re-formation is the price of surviving a broken world on runtimes
    without recoverability."""
    import ctypes

    from jax._src import distributed as _dist
    gs = _dist.global_state
    for obj in (gs.client, gs.service):
        if obj is not None:
            ctypes.pythonapi.Py_IncRef(ctypes.py_object(obj))
            _ABANDONED_RUNTIMES.append(obj)
    gs.client = None
    gs.service = None
    gs.preemption_sync_manager = None
    gs.coordinator_address = None


def shutdown_jax_distributed():
    import jax

    if getattr(init_jax_distributed, "_done", False):
        faultline.site("hvd.shutdown.pre_barrier")
        synchronized = _teardown_barrier()
        faultline.site("hvd.shutdown.post_barrier")
        if synchronized:
            _wait_for_members_to_leave()
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 - best-effort teardown
                pass
        else:
            _abandon_jax_distributed()
        # In-process elastic rejoin: the XLA backend cache still holds
        # clients built for the OLD world (gloo collectives with the
        # previous process set baked in), and jax.distributed.initialize
        # refuses to run once any backend exists.  Clearing the cache
        # lets the next init form the resized world; live jax.Arrays
        # from the old world become invalid, which is why elastic state
        # commits store host (numpy) copies.
        import jax.extend.backend
        jax.extend.backend.clear_backends()
        init_jax_distributed._done = False
