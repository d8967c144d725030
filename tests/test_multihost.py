"""Multihost-mode tests: N real processes × forced CPU devices joined in
ONE global JAX runtime.  The native core negotiates (control plane), the
multihost engine executes XLA collectives over the global mesh (payload
plane) — the reference's MPI-control/NCCL-payload split re-based on
``jax.distributed`` (SURVEY.md §2.6)."""

import os

import pytest

from tests.utils.spawn import assert_world_ok, spawn_world

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "utils",
                      "multihost_worker.py")


def _spawn_multihost(size, local_devices=4, extra_env=None, timeout=120,
                     worker=WORKER):
    env = {"HOROVOD_CONTROLLER": "multihost",
           "TEST_LOCAL_DEVICES": str(local_devices)}
    env.update(extra_env or {})
    # base+size+101 is the derived jax coordinator port
    # (common/multihost.py); probe it free along with the tcp-core range.
    return spawn_world(worker, size, extra_env=env, timeout=timeout,
                       extra_port_offsets=(size + 101,),
                       pop_env=("XLA_FLAGS",))


def _assert_ok(outs, marker="MULTIHOST_OK"):
    assert_world_ok(outs, marker)


@pytest.mark.parametrize("size", [2, 3])
def test_multihost_collective_matrix(size, tmp_path):
    # Full eager matrix over a real multi-process global mesh: fused and
    # grouped allreduce, every reduce op, ragged allgather/alltoall,
    # uneven reducescatter, process sets, join with zero contribution.
    # HVD_TPU_DUMP_HLO makes the worker also assert device payloads stay
    # device-resident and the programs lower to real collective HLO
    # (all_reduce / all_to_all / reduce_scatter).
    # TEST_TIMELINE_BASE additionally makes each worker assert its
    # chrome trace contains the executor's device-exec spans.
    # The r9 hier-op sections (all five eager collectives on the
    # proc x local plane) run on the 2-proc world only: the 3-proc
    # world re-covers nothing (same multi-proc x multi-local shape)
    # at ~3x the compile+gloo cost on this 1-core box, and the suite
    # must stay inside the tier-1 budget.
    _assert_ok(_spawn_multihost(size, extra_env={
        "HVD_TPU_DUMP_HLO": "1",
        "TEST_HIER_OPS": "1" if size == 2 else "0",
        "TEST_TIMELINE_BASE": str(tmp_path / "tl")}))


def test_multihost_single_local_device():
    # One device per process: the degenerate pod-of-single-chip-hosts
    # layout must behave identically.  The r9 hier-op sections are
    # skipped: the hier plane never engages at k=1, so the big
    # payloads would re-time the one-device plane for no coverage.
    _assert_ok(_spawn_multihost(2, local_devices=1,
                                extra_env={"TEST_HIER_OPS": "0"}))


COMPRESSION_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "utils",
    "multihost_compression_worker.py")


@pytest.mark.slow
def test_multihost_cross_host_compression_int8():
    # ISSUE 7 acceptance: with HOROVOD_CROSS_HOST_COMPRESSION=int8 the
    # hier legs of all five eager collectives put int8 (+ per-chunk f32
    # scales) on the cross-host wire — numerics inside the quantization
    # error bounds, error feedback canceling the error across repeated
    # steps, and mh_bus_bytes_total / mh_compression_ratio asserting a
    # >= 3.5x wire-byte reduction vs the uncompressed payload IN the
    # worker (not just printed).  Sub-threshold payloads stay flat,
    # uncompressed and bit-exact.  slow-marked per the r9/r10 gating
    # pattern (CI perf-smoke runs it by node id); the 2-proc x 4-local
    # world is the cheapest shape that exercises a real proc x local
    # mesh.
    _assert_ok(_spawn_multihost(2, extra_env={
        "HOROVOD_CROSS_HOST_COMPRESSION": "int8",
        "HVD_TPU_DUMP_HLO": "1",
    }, worker=COMPRESSION_WORKER), marker="MH_COMPRESSION_OK")


DP_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "utils", "multihost_dp_worker.py")


def test_multihost_data_parallel_step_matches_reference():
    # make_data_parallel_step over 2 processes x 2 devices: the update
    # must equal the single-process full-batch SGD step exactly (the
    # gradients are the global-batch mean by construction).
    _assert_ok(_spawn_multihost(2, local_devices=2, worker=DP_WORKER),
               marker="MH_DP_OK")


WATCHDOG_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "utils", "multihost_watchdog_worker.py")


def test_execution_watchdog_fails_survivors_loudly():
    # VERDICT r3 item 4: a member that wedges BETWEEN negotiation and
    # dispatch (alive, but never joining the compiled program — the
    # undetectable-on-ICI failure) blocks survivors inside the runtime
    # where the negotiation-phase stall inspector cannot see them.
    # Rank 1 negotiates the marked group but never dispatches; rank
    # 0's watchdog (HOROVOD_DEVICE_EXEC_TIMEOUT_SECONDS=6) must fail
    # the handle with a diagnostic naming the group, reject new work,
    # and let the process exit cleanly — all well inside the 60 s wait.
    outs = _spawn_multihost(2, local_devices=2, extra_env={
        "HOROVOD_DEVICE_EXEC_TIMEOUT_SECONDS": "6",
    }, worker=WATCHDOG_WORKER)
    rc0, out0, err0 = outs[0]
    rc1, out1, _err1 = outs[1]
    assert rc0 == 0, "survivor rank 0 failed (rc=%d):\n%s\n%s" % (
        rc0, out0, err0)
    assert "MH_WATCHDOG_OK 0" in out0, out0
    # Rank 1 wedged by design and dies when the coordination service
    # notices rank 0's exit — its exact exit code is runtime noise,
    # but it must never report success.
    assert rc1 != 0 and "MH_WATCHDOG_OK" not in out1, (rc1, out1)


SHUTDOWN_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "utils", "multihost_shutdown_worker.py")


@pytest.mark.parametrize("ordering", ["rank0_exits_first",
                                      "rank0_exits_last"])
def test_multihost_shutdown_ordering(ordering):
    # ISSUE 2 acceptance: hvd.init -> collective -> hvd.shutdown with
    # BOTH exit orderings is rc=0 on all ranks.  The synchronized
    # teardown barrier makes the ordering irrelevant: no rank starts
    # jax.distributed.shutdown() until every rank reached the barrier,
    # and a process exiting early can no longer FATAL a peer still
    # inside teardown (the r6 MULTICHIP RED).  Exit skew is 2 s —
    # far beyond the window the coordination service needs to notice a
    # missing peer.
    late = "1" if ordering == "rank0_exits_first" else "0"
    outs = _spawn_multihost(2, local_devices=2, extra_env={
        "TEST_EXIT_DELAY_RANK%s" % late: "2.0",
    }, worker=SHUTDOWN_WORKER)
    _assert_ok(outs, marker="MH_SHUTDOWN_OK")


def test_multihost_shutdown_skewed_arrival():
    # One rank reaches teardown 1.5 s late (injected at the pre-barrier
    # fault site): the punctual rank must WAIT at the barrier, not run
    # ahead into jax.distributed.shutdown() and exit under its peer.
    outs = _spawn_multihost(2, local_devices=2, extra_env={
        "HVD_TPU_FAULT": "hvd.shutdown.pre_barrier:delay:1.5@rank=0",
    }, worker=SHUTDOWN_WORKER)
    _assert_ok(outs, marker="MH_SHUTDOWN_OK")


FAULT_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "utils", "multihost_fault_worker.py")


def test_enqueue_legacy_order_fails_loudly_not_wrong():
    # The once-intermittent control-plane race, now deterministic:
    # core.enqueue.legacy_order reverses rank 1's enqueue to the
    # pre-fix ordering (Request visible to the controller BEFORE the
    # handle is parked) and holds the vulnerability window open 3 s.
    # Negotiation completes inside the window, so rank 1's negotiated
    # record names an unparked entry.  Pre-PR that zero-filled the
    # reduction (silent corruption, tests/README.md's "known
    # intermittent"); now the core refuses: the record carries an
    # error, the engine poisons itself, and EVERY rank either verifies
    # the correct sum or raises HorovodInternalError.  rank 0's side is
    # covered by the execution watchdog (it dispatched a program rank 1
    # never joins).  The 3 s window dwarfs any plausible negotiation
    # latency (the background loop is a C++ thread, not GIL-bound;
    # a 2-rank negotiation is one localhost round-trip), so the race
    # fires deterministically even on a loaded 1-core box.
    outs = _spawn_multihost(2, local_devices=1, extra_env={
        "HVD_TPU_FAULT": "core.enqueue.legacy_order:delay:3.0@rank=1",
        "HOROVOD_DEVICE_EXEC_TIMEOUT_SECONDS": "6",
    }, worker=FAULT_WORKER)
    for rank, (rc, out, err) in enumerate(outs):
        assert rc in (0, 3), \
            "rank %d neither correct nor loud (rc=%d):\n%s\n%s" % (
                rank, rc, out, err)
        if rc == 0:
            assert "FAULT_OK %d" % rank in out, out
        else:
            assert "FAULT_LOUD %d" % rank in out, out
    # The injected rank itself must have failed loudly, not silently.
    assert outs[1][0] == 3, outs[1][1] + outs[1][2]
    assert "refusing to zero-fill" in (outs[1][1] + outs[1][2])


def test_enqueue_fixed_order_delay_is_harmless():
    # A 500 ms delay at the FIXED ordering's seam (handle parked,
    # Request not yet visible): nothing can negotiate an unparked
    # entry, so the world completes correctly on every rank — the
    # ordering fix's proof point.
    outs = _spawn_multihost(2, local_devices=1, extra_env={
        "HVD_TPU_FAULT": "core.enqueue.pre_insert:delay:0.5@rank=1",
    }, worker=FAULT_WORKER)
    _assert_ok(outs, marker="FAULT_OK")


def _skew_totals(outs):
    """{rank: (lat_sum, count)} from the delay_skew scenario's
    SKEW_TOTALS report lines."""
    totals = {}
    for rank, (_rc, out, _err) in enumerate(outs):
        for line in out.splitlines():
            if line.startswith("SKEW_TOTALS "):
                _tag, r, total, count = line.split()
                totals[int(r)] = (float(total), int(count))
    return totals


@pytest.mark.slow
def test_drain_record_delay_completes_and_skews():
    # ISSUE 12 satellite: the `delay` action at the multihost DRAIN
    # seam (mh.drain.record — a negotiated record popped, dispatch
    # stalled; until now only die/drop/wedge paths were asserted
    # here).  A delayed-but-alive rank must COMPLETE every group with
    # correct values, not error it — and the delay must show up as
    # mh_collective_seconds skew: the t0 stamp sits AFTER this seam,
    # so the delayed rank's own window stays the exec-only fleet
    # minimum while the PROMPT rank's inflates by the wait (the
    # arrival-lag inversion the skew observatory scores).
    outs = _spawn_multihost(2, local_devices=1, extra_env={
        "HVD_TPU_FAULT": "mh.drain.record:delay:0.2@rank=1",
        "TEST_SCENARIO": "delay_skew",
    }, worker=FAULT_WORKER)
    _assert_ok(outs, marker="FAULT_OK")
    totals = _skew_totals(outs)
    assert set(totals) == {0, 1}, totals
    # Every group completed on both ranks (delayed != dropped).
    assert totals[0][1] >= 12 and totals[1][1] >= 12, totals
    # The prompt rank absorbed most of 12 x 0.2 s of waiting; the
    # delayed rank's own latency is a small fraction of it.
    assert totals[0][0] > 12 * 0.2 * 0.5, totals
    assert totals[0][0] > 3 * totals[1][0], totals


@pytest.mark.slow
def test_enqueue_delay_completes_without_skew():
    # The ENQUEUE seam's delay (mh.enqueue.pre_register): the payload
    # registers late, so NEGOTIATION stalls — but once negotiated,
    # both executors dispatch together, so the world completes
    # correctly with no per-rank latency skew (dispatch-to-completion
    # windows stay symmetric; the cost shows up as throughput, which
    # is exactly why the observatory keys on the dispatch seam's
    # signature rather than enqueue lag).
    outs = _spawn_multihost(2, local_devices=1, extra_env={
        "HVD_TPU_FAULT": "mh.enqueue.pre_register:delay:0.2@rank=1",
        "TEST_SCENARIO": "delay_skew",
    }, worker=FAULT_WORKER)
    _assert_ok(outs, marker="FAULT_OK")
    totals = _skew_totals(outs)
    assert totals[0][1] >= 12 and totals[1][1] >= 12, totals


def test_drain_drop_injection_trips_watchdog():
    # mh.drain.record:drop on rank 1 = a member that negotiates but
    # never dispatches (the alive-but-absent failure the execution
    # watchdog exists for), injected instead of hand-rolled in a
    # bespoke worker: rank 0 must fail loudly within the watchdog
    # window, never hang and never return a wrong value.
    outs = _spawn_multihost(2, local_devices=1, extra_env={
        "HVD_TPU_FAULT": "mh.drain.record:drop@rank=1",
        "HOROVOD_DEVICE_EXEC_TIMEOUT_SECONDS": "6",
    }, worker=FAULT_WORKER)
    rc0, out0, err0 = outs[0]
    assert rc0 == 3, "rank 0 should fail loudly (rc=%d):\n%s\n%s" % (
        rc0, out0, err0)
    assert "FAULT_LOUD 0" in out0, out0
    # Rank 1 dropped the record: its own handle never resolves and the
    # engine poisons on watchdog/stopped sweep — loud there too.
    rc1, out1, _err1 = outs[1]
    assert rc1 != 0 and "FAULT_OK" not in out1, (rc1, out1)


RESILIENCE_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "utils",
    "multihost_resilience_worker.py")


def test_leg_drop_bounded_is_absorbed_by_retry():
    # ISSUE 18 acceptance: a BOUNDED transport flake on rank 1's hier
    # leg (mh.leg.drop:drop@times=2) is absorbed by the transient-retry
    # budget — every group completes with the CORRECT value on every
    # rank, the victim's retry counter shows exactly the injected
    # count, and nothing was demoted.  The worker asserts the evidence
    # in-process (resilience.describe() + the path counters).
    _assert_ok(_spawn_multihost(2, local_devices=2, extra_env={
        "HVD_TPU_FAULT": "mh.leg.drop:drop@times=2@rank=1",
        "HOROVOD_LEG_RETRY_BACKOFF": "0.01",
        "TEST_SCENARIO": "leg_flake",
    }, worker=RESILIENCE_WORKER), marker="RESILIENCE_OK")


@pytest.mark.slow
def test_leg_drop_sustained_demotes_then_repromotes():
    # ISSUE 18 acceptance: a SUSTAINED leg fault (unbounded drop, every
    # rank) exhausts the retry budget twice, rank 0's KV verdict
    # demotes (allreduce, 131072) hier->flat SPMD-uniformly, a demoted
    # dispatch routes flat with no new retries, and after the fault is
    # disarmed the 1 s re-probe window re-promotes the class — the
    # final dispatch rides hier again.  The SPMD verdict needs a
    # rendezvous KV, so the test runs one in-process.
    from horovod_tpu.runner.http_server import RendezvousServer
    server = RendezvousServer(host="127.0.0.1", secret="s")
    port = server.start()
    try:
        _assert_ok(_spawn_multihost(2, local_devices=2, extra_env={
            "HVD_TPU_FAULT": "mh.leg.drop:drop",
            "HOROVOD_LEG_MAX_RETRIES": "1",
            "HOROVOD_LEG_RETRY_BACKOFF": "0.01",
            "HOROVOD_LEG_DEMOTE_THRESHOLD": "2",
            "HOROVOD_LEG_REPROBE_SECS": "1",
            "HOROVOD_RENDEZVOUS_ADDR": "127.0.0.1:%d" % port,
            "HOROVOD_SECRET_KEY": "s",
            "TEST_SCENARIO": "leg_demote",
        }, worker=RESILIENCE_WORKER), marker="RESILIENCE_OK")
    finally:
        server.stop()


def test_deadline_wedge_expires_loudly_with_restore_shaped_error():
    # ISSUE 18 acceptance: mh.deadline.wedge withholds the dispatch of
    # a negotiated, deadline-stamped group on every rank — the exact
    # shape of a program that never starts.  The per-collective
    # deadline (4 s) must expire it: every rank fails LOUDLY with the
    # deadline-shaped HorovodInternalError, and the message must NOT
    # be the stall inspector's drain-shaped abort text (elastic keys on
    # that phrase to pick drain vs restore-from-spill).
    outs = _spawn_multihost(2, local_devices=1, extra_env={
        "HVD_TPU_FAULT": "mh.deadline.wedge:drop@times=1",
        "HOROVOD_COLLECTIVE_TIMEOUT_SECS": "4",
    }, worker=FAULT_WORKER)
    for rank, (rc, out, err) in enumerate(outs):
        assert rc == 3, "rank %d should fail loudly (rc=%d):\n%s\n%s" \
            % (rank, rc, out, err)
        assert "FAULT_LOUD %d" % rank in out, out
        assert "collective deadline exceeded" in out, out
        assert "stall shutdown threshold" not in out + err, out + err


FASTPATH_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "utils",
    "multihost_fastpath_worker.py")


def _spawn_fastpath(scenario, extra_env=None):
    # Every fast-path scenario needs the rendezvous KV: the freeze
    # verdict is rank-0-decided and KV-adopted (a KV-less multi-member
    # world never freezes by design), so run a server in-process.
    from horovod_tpu.runner.http_server import RendezvousServer
    server = RendezvousServer(host="127.0.0.1", secret="s")
    port = server.start()
    env = {
        "HOROVOD_FAST_PATH_WARM_CYCLES": "3",
        "HOROVOD_RENDEZVOUS_ADDR": "127.0.0.1:%d" % port,
        "HOROVOD_SECRET_KEY": "s",
        "TEST_SCENARIO": scenario,
    }
    env.update(extra_env or {})
    try:
        _assert_ok(_spawn_multihost(2, local_devices=2, extra_env=env,
                                    worker=FASTPATH_WORKER),
                   marker="FASTPATH_OK")
    finally:
        server.stop()


def test_fastpath_shape_change_thaws_and_refreezes():
    # ISSUE 19 acceptance: after the warm streak the engine dispatches
    # from the frozen schedule (frozen counter moves, negotiation-cycle
    # counter does not — the satellite-f reconciliation), a mismatching
    # shape thaws loudly with the correct renegotiated value, and the
    # engine re-freezes on the new shape.
    _spawn_fastpath("fp_shape")


def test_fastpath_membership_change_thaws():
    # ISSUE 19 acceptance: the elastic-resize-shaped membership change
    # (process-set removal -> engine invalidation) thaws the frozen
    # schedule with reason=membership before the engine mutates its
    # pending map; the world keeps reducing correctly and re-freezes.
    _spawn_fastpath("fp_membership")


def test_fastpath_stale_dispatch_injection_thaws():
    # ISSUE 19 acceptance (injection-certified): the armed
    # engine.fastpath.stale_dispatch site drops the first frozen bucket
    # dispatch — thaw(staleness), the staged tensor flushes back
    # through full negotiation (correct value, NO hang), and the engine
    # re-freezes after every rank disarms.
    _spawn_fastpath("fp_stale", extra_env={
        "HVD_TPU_FAULT": "engine.fastpath.stale_dispatch:drop@times=1",
    })


def test_fastpath_route_demote_verdict_thaws():
    # ISSUE 19 acceptance: the r21 degraded-route demote verdict
    # (rank 0 streak through the KV) thaws the frozen schedule on every
    # member BEFORE the plan invalidate; post-thaw dispatches
    # renegotiate onto the demoted flat route with correct values.
    _spawn_fastpath("fp_route", extra_env={
        "HVD_TPU_FAULT": "mh.leg.drop:drop",
        "HOROVOD_LEG_MAX_RETRIES": "1",
        "HOROVOD_LEG_RETRY_BACKOFF": "0.01",
        "HOROVOD_LEG_DEMOTE_THRESHOLD": "2",
    })


def test_init_detects_preinitialized_runtime(monkeypatch):
    # A pre-initialized JAX backend makes jax.distributed.initialize a
    # silent no-op: every rank would train alone while believing it is
    # rank r of N.  init_jax_distributed must detect the world that
    # failed to form and raise, not proceed.
    import types

    from horovod_tpu.common import multihost as mh

    fake_jax = types.SimpleNamespace(
        config=types.SimpleNamespace(
            update=lambda *a, **k: None, jax_platforms="cpu"),
        distributed=types.SimpleNamespace(
            initialize=lambda **kw: None),  # the silent no-op
        process_count=lambda: 1,            # world never formed
    )
    monkeypatch.setattr(mh, "init_jax_distributed",
                        mh.init_jax_distributed)
    monkeypatch.setitem(__import__("sys").modules, "jax", fake_jax)
    monkeypatch.setattr(mh.init_jax_distributed, "_done", False,
                        raising=False)
    cfg = types.SimpleNamespace(coordinator_addr="127.0.0.1:1",
                                rendezvous_addr=None, secret_key=None)
    import pytest as _pytest
    with _pytest.raises(RuntimeError, match="train alone|initialized "
                                            "before|process_count"):
        mh.init_jax_distributed(cfg, rank=0, size=2)
    mh.init_jax_distributed._done = False


ZERO_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "utils",
    "zero_mh_worker.py")


@pytest.mark.slow
def test_multihost_zero23_quantized_e2e():
    # ISSUE 15: ZeRO-2/3 step builders over the REAL proc x local mesh
    # (2 procs x 2 local devices) with the int8 DCN leg armed —
    # position-dependent payloads vs a single-device reference within
    # the EF bounds, per-tensor EF residuals present, and (via
    # HVD_TPU_DUMP_HLO) the lowered programs spanning all
    # n_procs x n_local partitions with reduce-scatter/all-gather HLO
    # and an s8 wire.
    _assert_ok(_spawn_multihost(2, local_devices=2, worker=ZERO_WORKER,
                                extra_env={
        "HOROVOD_CROSS_HOST_COMPRESSION": "int8",
        "HVD_TPU_DUMP_HLO": "1",
    }))
