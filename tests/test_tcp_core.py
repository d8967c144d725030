"""Multi-process native-core tests: a real N-process world on localhost
(reference test strategy: Gloo-on-localhost IS the test backend,
SURVEY.md §4)."""

import os

import pytest

from tests.utils.spawn import assert_world_ok, spawn_world

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "utils",
                      "tcp_worker.py")


def _spawn_world(size, scenario, extra_env=None, timeout=60):
    env = {"TEST_SCENARIO": scenario}
    env.update(extra_env or {})
    return spawn_world(WORKER, size, extra_env=env, timeout=timeout)


def _assert_ok(outs):
    assert_world_ok(outs)


@pytest.mark.parametrize("size", [2, 4])
def test_tcp_collective_matrix(size):
    _assert_ok(_spawn_world(size, "collectives"))


def test_tcp_response_cache_fast_path():
    _assert_ok(_spawn_world(2, "cache"))


def test_tcp_cache_eviction_under_capacity_pressure():
    # LRU eviction with id reuse must stay rank-identical (evictions
    # follow broadcast order); 10 rotating tensors against capacity 4.
    _assert_ok(_spawn_world(2, "cache_evict",
                            extra_env={"HOROVOD_CACHE_CAPACITY": "4"}))


@pytest.mark.parametrize("size", [2, 4])
def test_tcp_group_name_reuse_changed_membership(size):
    # Regression: reusing a grouped_allreduce name with different member
    # count/shapes deadlocked — cached members bypassed the group
    # barrier while the shape-changed member waited in pending forever.
    # Size 4 adds process-set-scoped grouped negotiation.
    _assert_ok(_spawn_world(size, "regroup"))


def test_tcp_join_uneven_data():
    _assert_ok(_spawn_world(3, "join"))


def test_tcp_error_propagation():
    _assert_ok(_spawn_world(2, "error"))


def test_tcp_collective_deadline_distinct_abort():
    # ISSUE 18 C++ mirror: HOROVOD_COLLECTIVE_TIMEOUT_SECS bounds a
    # negotiation-phase hang in the native core too (python-less
    # worlds).  A tensor only rank 0 submits must error-complete after
    # the deadline with "collective deadline exceeded" — a message
    # DISTINCT from the stall inspector's drain-shaped abort, because
    # elastic routes the two differently (restore vs drain).
    outs = _spawn_world(2, "deadline", extra_env={
        "HOROVOD_COLLECTIVE_TIMEOUT_SECS": "2",
    })
    assert_world_ok(outs, marker="DEADLINE_OK")


def test_tcp_timeline_written(tmp_path):
    tl = str(tmp_path / "tl.json")
    _assert_ok(_spawn_world(2, "cache", extra_env={"HOROVOD_TIMELINE": tl}))
    import json
    events = json.load(open(tl + ".0"))
    assert any(e.get("name", "").startswith("NEGOTIATE") for e in events)
    assert any(e.get("name") == "ALLREDUCE" for e in events)


def test_core_library_builds():
    from horovod_tpu.core.client import core_library_available
    assert core_library_available()


def test_world_reinit():
    """Shutdown → init must yield a working fresh world (the elastic
    path); regression: controller shutdown/join rank-sets leaking across
    worlds killed the new background loop after one cycle."""
    import time
    import horovod_tpu.torch as hvd
    import torch
    for w in range(2):
        hvd.init()
        time.sleep(0.2)  # let a few negotiation cycles run
        out = hvd.broadcast(torch.ones(2), 0, name="reinit_b%d" % w)
        assert torch.equal(out, torch.ones(2))
        hvd.shutdown()


def test_tcp_hierarchical_allreduce():
    # fake a 2-host x 2-slot topology on localhost: intra-host ring,
    # leader ring across "hosts", intra-host broadcast — results must
    # match the flat ring exactly
    _assert_ok(_spawn_world(4, "collectives", extra_env={
        "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
        "HVD_TPU_HOST_OF_RANK": "0,0,1,1",
    }))


def test_tcp_hierarchical_uneven_groups():
    # 3 ranks on host0, 1 on host1 (uneven groups + singleton leader)
    _assert_ok(_spawn_world(4, "collectives", extra_env={
        "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
        "HVD_TPU_HOST_OF_RANK": "0,0,0,1",
    }))


def test_tcp_autotune_samples_written(tmp_path):
    # rank 0 runs the BO autotuner in the C++ core: with pacing lowered
    # it must SCORE samples (data rows), not just write the csv header.
    # The r14 crash-safe writer rank-stamps the path (".r<rank>", one
    # writer per file, O_APPEND) so concurrent worlds never interleave.
    log = str(tmp_path / "autotune.csv")
    _assert_ok(_spawn_world(2, "autotune", extra_env={
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_LOG": log,
        "HVD_TPU_AUTOTUNE_WARMUP_CYCLES": "1",
        "HVD_TPU_AUTOTUNE_CYCLES_PER_SAMPLE": "2",
    }))
    assert not os.path.exists(log)  # no writer at the raw path anymore
    lines = open(log + ".r0").read().strip().splitlines()
    assert lines[0].startswith("sample,")
    assert len(lines) >= 3, lines  # header + >=2 scored samples
    # A rerun sharing the log path appends instead of clobbering, and
    # the header is not restamped.
    _assert_ok(_spawn_world(2, "autotune", extra_env={
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_LOG": log,
        "HVD_TPU_AUTOTUNE_WARMUP_CYCLES": "1",
        "HVD_TPU_AUTOTUNE_CYCLES_PER_SAMPLE": "2",
    }))
    lines2 = open(log + ".r0").read().strip().splitlines()
    assert len(lines2) > len(lines), (lines, lines2)
    assert sum(1 for ln in lines2 if ln.startswith("sample,")) == 1


def test_tcp_hierarchical_interleaved_hosts():
    # ranks alternate hosts (0,1,0,1): group blocks are NON-contiguous
    # in member order, so this catches any ordering mistake in the
    # hierarchical allgather/allreduce paths
    _assert_ok(_spawn_world(4, "collectives", extra_env={
        "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
        "HVD_TPU_HOST_OF_RANK": "0,1,0,1",
    }))


def test_tcp_hierarchical_big_allgather():
    # G=2 leader exchange with multi-MB payloads: completes only with
    # the ordered send/recv protocol (simultaneous blocking sends
    # would deadlock once socket buffers fill)
    _assert_ok(_spawn_world(4, "big_allgather", extra_env={
        "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
        "HVD_TPU_HOST_OF_RANK": "0,0,1,1",
    }))


def test_tcp_hierarchical_allgather_own_knob():
    # HOROVOD_HIERARCHICAL_ALLGATHER selects the allgather algorithm
    # independently of the allreduce knob (reference exposes both).
    _assert_ok(_spawn_world(4, "big_allgather", extra_env={
        "HOROVOD_HIERARCHICAL_ALLREDUCE": "0",
        "HOROVOD_HIERARCHICAL_ALLGATHER": "1",
        "HVD_TPU_HOST_OF_RANK": "0,0,1,1",
    }))


EXTERNAL_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "utils", "external_worker.py")


def _spawn_external_world(size, scenario, timeout=60):
    return spawn_world(EXTERNAL_WORKER, size,
                       extra_env={"TEST_SCENARIO": scenario},
                       timeout=timeout)


@pytest.mark.parametrize("size", [2, 3])
def test_external_payload_negotiation_order(size):
    # Device-payload ops: negotiation must deliver one identical
    # execution order on every rank (verified cross-rank by the worker).
    _assert_ok(_spawn_external_world(size, "order"))


def test_external_payload_mixed_with_host_ops():
    # External and host ops interleave; external never fuses with host,
    # executor failures surface through the handle.
    _assert_ok(_spawn_external_world(2, "mixed"))


# -- sanitizer leg ----------------------------------------------------------

CORE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(WORKER)))), "horovod_tpu", "core")


def _sanitized_env(kind, runtime_so):
    """Spawn env for a sanitized world: the instrumented core is
    dlopen'd into an UNinstrumented python, so the sanitizer runtime
    must be preloaded, and python must use raw malloc — pymalloc's
    arena-internal reuse is invisible to the runtime and leaves stale
    sync metadata on reused addresses (phantom reports)."""
    import subprocess
    try:
        out = subprocess.run(
            ["g++", "-print-file-name=%s" % runtime_so],
            capture_output=True, check=True, timeout=60,
            text=True).stdout.strip()
    except Exception:
        return None
    if not os.path.isabs(out):  # "libtsan.so" echoed back: not found
        return None
    return {"LD_PRELOAD": out, "PYTHONMALLOC": "malloc"}


def _sanitized_lib(kind):
    """Build the side-by-side instrumented core (make SANITIZE=<kind>),
    or None when the toolchain can't produce it (missing libtsan etc.)
    — the caller skips rather than fails."""
    import subprocess
    try:
        subprocess.run(["make", "-s", "-j", "SANITIZE=%s" % kind],
                       cwd=CORE_DIR, check=True, capture_output=True,
                       timeout=300)
    except Exception:
        return None
    lib = os.path.join(CORE_DIR, "libhvdtpu_core_%s.so" % kind)
    return lib if os.path.exists(lib) else None


@pytest.mark.slow
def test_tcp_collectives_under_tsan():
    """Full 2-proc collective matrix against a ThreadSanitizer build:
    the enqueue / background-negotiation / completion threads must be
    race-free under real interleavings, not just under the lock graph
    graftlint certifies statically.  halt_on_error turns any report
    into a nonzero worker exit the harness rejects."""
    lib = _sanitized_lib("thread")
    env = _sanitized_env("thread", "libtsan.so")
    if lib is None or env is None:
        pytest.skip("TSan core build unavailable")
    supp = os.path.join(os.path.dirname(os.path.abspath(WORKER)),
                        "tsan.supp")
    env.update({
        "HVD_TPU_CORE_LIB": lib,
        "TSAN_OPTIONS":
            "halt_on_error=1 exitcode=66 suppressions=%s" % supp,
    })
    _assert_ok(_spawn_world(2, "collectives", extra_env=env,
                            timeout=150))


@pytest.mark.slow
def test_tcp_collectives_under_asan():
    """Same matrix under AddressSanitizer: wire (de)serialization and
    the fusion-buffer copies stay in bounds."""
    lib = _sanitized_lib("address")
    env = _sanitized_env("address", "libasan.so")
    if lib is None or env is None:
        pytest.skip("ASan core build unavailable")
    env.update({
        "HVD_TPU_CORE_LIB": lib,
        # leak detection off: the long-lived CoreState singleton and
        # python interpreter allocations are intentional.
        "ASAN_OPTIONS": "halt_on_error=1:exitcode=66:detect_leaks=0",
    })
    _assert_ok(_spawn_world(2, "collectives", extra_env=env,
                            timeout=150))
