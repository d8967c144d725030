"""Fail-fast worker for the enqueue-ordering injection tests: one
allreduce whose result is either verified CORRECT (exit 0, marker
FAULT_OK) or failed LOUDLY with HorovodInternalError (exit 3, marker
FAULT_LOUD).  Any other outcome — a silently wrong reduction above all
— is a plain failure (assertion, rc 1).

The spawning test arms HVD_TPU_FAULT (e.g. core.enqueue.legacy_order,
the pre-fix enqueue ordering) and asserts the world never completes
with a corrupted value: loud errors are the acceptable failure mode,
wrong numbers never are.

``TEST_SCENARIO=delay_skew`` runs the delayed-but-alive leg instead:
a burst of verified allreduces under an armed ``delay`` action at a
multihost dispatch seam, followed by a ``SKEW_TOTALS <rank> <sum>
<count>`` report of this rank's ``mh_collective_seconds`` totals —
the spawning test asserts the delayed rank completed every group
(values correct, no error path) AND that the delay is visible as
latency skew (the PROMPT rank's window inflates by the wait; the
delayed rank's own dispatch→completion stays the fleet minimum — the
arrival-lag inversion common/skew.py scores)."""

import os
import sys
import time

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("TEST_LOCAL_DEVICES", "2")).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np

import horovod_tpu as hvd
from horovod_tpu.ops.engine import HorovodInternalError


def run_delay_skew():
    hvd.init(controller="multihost")
    r, n = hvd.rank(), hvd.size()
    expected = float(sum(range(1, n + 1)))
    for i in range(12):
        out = hvd.allreduce(np.full((64,), float(r + 1), np.float32),
                            op=hvd.Sum, name="skew%d" % i)
        np.testing.assert_allclose(np.asarray(out), expected)
    from horovod_tpu.common import skew
    from horovod_tpu.common.metrics import snapshot
    total, count = skew._hist_totals(snapshot(),
                                     "mh_collective_seconds")
    print("SKEW_TOTALS %d %.6f %d" % (r, total, int(count)),
          flush=True)
    hvd.shutdown()
    print("FAULT_OK %d" % r, flush=True)


def main():
    if os.environ.get("TEST_SCENARIO") == "delay_skew":
        run_delay_skew()
        return
    hvd.init(controller="multihost")
    r, n = hvd.rank(), hvd.size()
    try:
        out = hvd.allreduce(np.full((8,), float(r + 1), np.float32),
                            op=hvd.Sum, name="inj")
    except HorovodInternalError as exc:
        print("FAULT_LOUD %d: %s" % (r, exc), flush=True)
        # Every rank stamps a collective's deadline on its own clock, and
        # on a loaded box the ranks run seconds apart: a rank that left
        # the moment its own deadline fired would be a disconnected
        # member to a peer whose deadline has not come yet.  Stay for
        # one more deadline (nothing, where the test sets none), and
        # rank 0 for two: it holds the coordination service, and the
        # runtime kills whoever is still connected when that goes.
        time.sleep((2 if r == 0 else 1) * float(
            os.environ.get("HOROVOD_COLLECTIVE_TIMEOUT_SECS") or 0))
        # Loud failure is a legitimate outcome under injection; the
        # world is poisoned, so skip hvd.shutdown()'s collective
        # teardown and exit with the designated code.
        os._exit(3)
    expected = float(sum(range(1, n + 1)))
    np.testing.assert_allclose(np.asarray(out), expected)
    hvd.shutdown()
    print("FAULT_OK %d" % r, flush=True)


if __name__ == "__main__":
    main()
