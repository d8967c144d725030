"""Worker process for multi-process TCP core tests (run by
test_tcp_core.py as a real subprocess world, the way the reference tests
run under `horovodrun -np 2 pytest` with Gloo-on-localhost)."""

import faulthandler
import os
import sys

# A world of these has been seen to stop without a word (four ranks, under
# load, one file run in ten): before the harness's limit every thread says
# where it stands, and the harness shows it (spawn.py: world_timed_out).
faulthandler.dump_traceback_later(40, exit=False)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

from horovod_tpu.common.topology import multiprocess_topology
from horovod_tpu.common.config import Config
from horovod_tpu.core.client import TcpCore
from horovod_tpu.ops.engine import HorovodInternalError


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    scenario = os.environ.get("TEST_SCENARIO", "all")
    topo = multiprocess_topology(rank, size)
    core = TcpCore(topo, Config.from_env())
    core.initialize()
    try:
        if scenario in ("all", "collectives"):
            run_collectives(core, rank, size)
        if scenario in ("all", "cache"):
            run_cache(core, rank, size)
        if scenario == "big_allgather":
            run_big_allgather(core, rank, size)
        if scenario == "regroup":
            run_regroup(core, rank, size)
        if scenario == "cache_evict":
            run_cache_evict(core, rank, size)
        if scenario == "autotune":
            run_autotune(core, rank, size)
        if scenario == "join":
            run_join(core, rank, size)
        if scenario == "error":
            run_error(core, rank, size)
        if scenario == "deadline":
            run_deadline(core, rank, size)
    finally:
        core.shutdown()


def run_collectives(core, rank, size):
    # allreduce sum, fused small tensors.
    handles = []
    for i, n in enumerate((3, 5, 1000)):
        x = np.full((n,), float(rank + 1 + i), dtype=np.float32)
        handles.append(core.allreduce_async(x, "ar.%d" % i))
    for i, n in enumerate((3, 5, 1000)):
        out = handles[i].wait(timeout=30)
        expected = sum(r + 1 + i for r in range(size))
        assert out.shape == (n,), out.shape
        np.testing.assert_allclose(out, expected)
    # average with prescale/postscale.
    x = np.full((4,), float(rank), dtype=np.float64)
    out = core.allreduce_async(x, "avg", op="Average", prescale=2.0,
                               postscale=0.5).wait(timeout=30)
    np.testing.assert_allclose(
        out, 2.0 * np.mean(np.arange(size)) * 0.5)
    # min / max / product / int32.
    x = np.array([rank + 1], dtype=np.int32)
    assert core.allreduce_async(x, "min", op="Min").wait(30)[0] == 1
    assert core.allreduce_async(x, "max", op="Max").wait(30)[0] == size
    prod = core.allreduce_async(
        np.array([2.0], np.float32), "prod", op="Product").wait(30)
    np.testing.assert_allclose(prod, [2.0 ** size])
    # adasum (identical vectors collapse to one copy).
    same = np.arange(8, dtype=np.float32)
    out = core.allreduce_async(same, "adasum", op="Adasum").wait(30)
    np.testing.assert_allclose(out, same, rtol=1e-5)
    # allgather, ragged first dim: rank r contributes r+1 rows.
    x = np.full((rank + 1, 2), rank, dtype=np.float32)
    out = core.allgather_async(x, "ag").wait(timeout=30)
    assert out.shape == (sum(r + 1 for r in range(size)), 2)
    expected = np.concatenate(
        [np.full((r + 1, 2), r, np.float32) for r in range(size)])
    np.testing.assert_allclose(out, expected)
    # broadcast from root 1.
    x = (np.arange(6, dtype=np.float32).reshape(2, 3) if rank == 1
         else np.zeros((2, 3), np.float32))
    out = core.broadcast_async(x, "bc", root_rank=1).wait(timeout=30)
    np.testing.assert_allclose(
        out, np.arange(6, dtype=np.float32).reshape(2, 3))
    # alltoall with ragged splits: rank r sends (j+1) rows to rank j.
    splits = [j + 1 for j in range(size)]
    rows = sum(splits)
    x = np.full((rows, 2), rank, dtype=np.float32)
    out, recv_splits = core.alltoall_async(x, "a2a",
                                           splits=splits).wait(timeout=30)
    assert recv_splits == [rank + 1] * size, recv_splits
    assert out.shape == ((rank + 1) * size, 2)
    expected_col = np.repeat(np.arange(size, dtype=np.float32), rank + 1)
    np.testing.assert_allclose(out[:, 0], expected_col)
    # reducescatter with uneven first dim (size*2+1 rows).
    d0 = size * 2 + 1
    x = np.tile(np.arange(d0, dtype=np.float32)[:, None], (1, 3))
    out = core.reducescatter_async(x, "rs").wait(timeout=30)
    base, rem = divmod(d0, size)
    my_rows = base + (1 if rank < rem else 0)
    start = rank * base + min(rank, rem)
    assert out.shape == (my_rows, 3), out.shape
    np.testing.assert_allclose(
        out, size * np.tile(
            np.arange(start, start + my_rows,
                      dtype=np.float32)[:, None], (1, 3)))
    # barrier + process-set collective on even ranks.
    core.barrier("b1")
    ps = core.add_process_set(list(range(0, size, 2)))
    if rank % 2 == 0:
        x = np.full((3,), float(rank), np.float32)
        out = core.allreduce_async(x, "ps_ar", process_set_id=ps).wait(30)
        np.testing.assert_allclose(
            out, sum(float(r) for r in range(0, size, 2)))
    core.barrier("b2")
    # object helpers.
    objs = core.allgather_object({"rank": rank})
    assert [o["rank"] for o in objs] == list(range(size))
    obj = core.broadcast_object({"val": rank * 10}, root_rank=0)
    assert obj == {"val": 0}


def run_cache(core, rank, size):
    # Same tensor reduced repeatedly: second and later rounds must ride
    # the bitvector cache path (hits grow, misses stay flat).
    x = np.full((64,), float(rank), np.float32)
    core.allreduce_async(x, "steady").wait(30)
    h0, m0 = core.cache_stats() if rank == 0 else (0, 0)
    for it in range(5):
        out = core.allreduce_async(x, "steady").wait(30)
        np.testing.assert_allclose(out, sum(range(size)))
    if rank == 0:
        h1, m1 = core.cache_stats()
        assert h1 - h0 >= 5, (h0, h1)
        assert m1 == m0, (m0, m1)


def run_regroup(core, rank, size):
    # Group-name reuse with changed membership/shapes: grouped members
    # must not ride the response-cache bit path — a cached member would
    # complete solo while cache-missing groupmates wait on the group
    # barrier forever (the r3 deadlock this scenario regression-tests).
    def grouped(tensors):
        names = ["g.%d" % i for i in range(len(tensors))]
        core.register_group(names)
        hs = [core.allreduce_async(t, n) for t, n in zip(tensors, names)]
        return [h.wait(timeout=30) for h in hs]

    outs = grouped([np.ones(8, np.float32), np.ones((8, 4), np.float32),
                    np.ones((3, 8), np.float32)])
    for o in outs:
        np.testing.assert_allclose(o, float(size))
    # Same base name, fewer members, g.1 changes shape entirely.
    outs = grouped([np.ones(8, np.float32) * 2,
                    np.ones((2,), np.float32) * 2])
    for o in outs:
        np.testing.assert_allclose(o, 2.0 * size)
    # Steady-state reuse with identical layout still completes (grouped
    # names stay uncacheable; correctness over the bit path).
    for _ in range(3):
        outs = grouped([np.ones(8, np.float32), np.ones((2,), np.float32)])
        for o in outs:
            np.testing.assert_allclose(o, float(size))
    # Grouped allgather and reducescatter negotiate atomically too
    # (reference v0.28 grouped variants; ragged first member).
    names = ["gag.0", "gag.1"]
    core.register_group(names)
    hs = [core.allgather_async(
        np.full((rank + 1, 2), float(rank), np.float32), names[0]),
        core.allgather_async(np.full((3,), float(rank), np.float32),
                             names[1])]
    g0, g1 = [h.wait(timeout=30) for h in hs]
    assert g0.shape == (size * (size + 1) // 2, 2)
    assert g1.shape == (3 * size,)
    names = ["grs.0", "grs.1"]
    core.register_group(names)
    hs = [core.reducescatter_async(
        np.arange(size * 2, dtype=np.float32), names[0]),
        core.reducescatter_async(
            np.ones(size, np.float32) * (rank + 1), names[1])]
    r0, r1 = [h.wait(timeout=30) for h in hs]
    np.testing.assert_allclose(
        r0, np.arange(size * 2, dtype=np.float32)[
            rank * 2:(rank + 1) * 2] * size)
    np.testing.assert_allclose(r1, sum(range(1, size + 1)))
    if size >= 4:
        # Grouped collective scoped to a process set (even ranks):
        # atomic negotiation within the subgroup while odd ranks sit
        # out entirely.
        ps = core.add_process_set([0, 2])
        if rank in (0, 2):
            names = ["psg.0", "psg.1"]
            core.register_group(names)
            hs = [core.allreduce_async(
                np.ones(3, np.float32) * (rank + 1), names[0],
                process_set_id=ps),
                core.allreduce_async(np.ones(2, np.float32), names[1],
                                     process_set_id=ps)]
            o0, o1 = [h.wait(timeout=30) for h in hs]
            np.testing.assert_allclose(o0, 4.0)  # ranks 1 + 3
            np.testing.assert_allclose(o1, 2.0)
        core.barrier("psg_done")


def run_cache_evict(core, rank, size):
    # Capacity overflow: 10 rotating names against HOROVOD_CACHE_
    # CAPACITY=4 force constant LRU eviction + id reuse; correctness
    # requires every rank to assign/evict identically (broadcast
    # order), with a hot tensor pinned at the LRU front throughout.
    for round_ in range(6):
        hot = core.allreduce_async(
            np.full((8,), float(rank + round_), np.float32),
            "hot").wait(30)
        np.testing.assert_allclose(
            hot, sum(r + round_ for r in range(size)))
        for i in range(10):
            x = np.full((4,), float(rank + 1 + i), np.float32)
            out = core.allreduce_async(x, "rot.%d" % i).wait(30)
            np.testing.assert_allclose(
                out, sum(r + 1 + i for r in range(size)))
    # Shape change on a cached-then-evicted-then-reused name still
    # negotiates (LookupMatching guards shape).
    out = core.allreduce_async(
        np.full((2, 3), float(rank), np.float32), "rot.0").wait(30)
    np.testing.assert_allclose(out, sum(range(size)))


def run_autotune(core, rank, size):
    # steady allreduce traffic long enough for the BO autotuner to
    # complete several samples (pacing lowered via env in the test)
    x = np.full((4096,), float(rank), np.float32)
    for it in range(30):
        core.allreduce_async(x, "tune.%d" % (it % 3)).wait(30)


def run_big_allgather(core, rank, size):
    # multi-MB blocks: leader group exchange far exceeds socket
    # buffering, so only the ordered (parity) send/recv protocol
    # completes — guards the hierarchical-allgather deadlock case
    rows = 250_000  # 1 MB per rank (f32), 2-4 MB group payloads
    x = np.full((rows,), float(rank), np.float32)
    out = core.allgather_async(x, "big_ag").wait(timeout=120)
    assert out.shape == (rows * size,)
    for r in range(size):
        assert out[r * rows] == float(r)
        assert out[(r + 1) * rows - 1] == float(r)


def run_join(core, rank, size):
    # Uneven data: rank r has r+1 batches; after its last batch each rank
    # joins; allreduces keep working with joined ranks contributing zeros.
    for step in range(rank + 1):
        x = np.full((4,), 1.0, np.float32)
        core.allreduce_async(x, "j.%d.%d" % (rank, step))
    # Submit-then-join: every rank contributes real data to this Min
    # BEFORE joining (per-rank FIFO guarantees the request precedes the
    # join), so no zero-fill happens and the op must succeed.
    h = core.allreduce_async(np.full((4,), float(rank + 1), np.float32),
                             "jminok", op="Min")
    out = h.wait(timeout=120)
    assert np.allclose(out, 1.0), out
    if rank > 0 and size > 1:
        # Rank 0 has joined (or will before this becomes ready: it never
        # submits "jmin", so readiness requires its join).  Zero is not
        # Min's identity — the controller must error, not corrupt.
        h = core.allreduce_async(np.full((4,), 5.0, np.float32), "jmin",
                                 op="Min")
        try:
            h.wait(timeout=120)
            raise AssertionError("Min allreduce with joined rank "
                                 "should have errored")
        except HorovodInternalError as e:
            assert "Sum/Average" in str(e), str(e)
        # Average over the live contributors: rank 0 is joined and
        # missing, so the divisor is size-1, not size.
        h = core.allreduce_async(np.full((4,), float(rank), np.float32),
                                 "javg", op="Average")
        out = h.wait(timeout=120)
        expect = sum(range(1, size)) / float(size - 1)
        assert np.allclose(out, expect), (out, expect)
    # Everyone joins after its own work; join returns the last rank.
    last = core.join()
    assert 0 <= last < size


def run_deadline(core, rank, size):
    # A collective rank 0 submits but rank 1+ withholds: the native
    # core's per-collective deadline (HOROVOD_COLLECTIVE_TIMEOUT_SECS,
    # the C++ mirror of common/resilience.py) must error-complete it
    # with the RESTORE-shaped message — never the drain-shaped stall
    # text elastic keys on, and never a hang.
    import time
    budget = float(os.environ.get("HOROVOD_COLLECTIVE_TIMEOUT_SECS", "2"))
    if rank == 0:
        h = core.allreduce_async(np.ones(4, np.float32), "dl")
        try:
            h.wait(timeout=60)
            raise AssertionError("deadline should have expired")
        except HorovodInternalError as e:
            msg = str(e)
            assert "collective deadline exceeded" in msg, msg
            assert "stall shutdown threshold" not in msg, msg
    else:
        # Stay alive past rank 0's expiry so the world's teardown is
        # orderly (a dead peer would be a different failure mode).
        time.sleep(budget + 2.0)
    print("DEADLINE_OK %d" % rank, flush=True)


def run_error(core, rank, size):
    # Mismatched shapes across ranks must surface an error, not a hang.
    x = np.zeros((rank + 1,), np.float32)  # different shape per rank
    try:
        core.allreduce_async(x, "bad").wait(timeout=30)
        assert size == 1, "expected HorovodInternalError"
    except HorovodInternalError as e:
        assert "Mismatched" in str(e) or "shape" in str(e).lower()


if __name__ == "__main__":
    main()
