"""Skew observatory tests (ISSUE 12): the observe→decide→act loop.

Fast units drive synthetic fleet snapshots through the analyzer /
observatory / staleness tracker and the plancache actuation seams; the
slow-marked e2e closes the real loop — an injected dispatch-seam delay
on one host of a live elastic multihost world must produce a
``straggler_detected`` event, a drain actuation through the r10
planned-removal path, and a recovered world.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from horovod_tpu.common import metrics, skew
from tests.utils.spawn import run_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.reset()
    yield
    metrics.reset()


def _model(lat_sum, lat_count, qdepth=None, group=None):
    """A minimal snapshot model with cumulative mh_collective_seconds
    totals (what one worker's pull returns)."""
    model = {"mh_collective_seconds": {
        "kind": "histogram", "help": "",
        "series": [{"labels": {"op": "allreduce",
                               "size_class": "65536"},
                    "buckets": {}, "sum": lat_sum,
                    "count": lat_count}]}}
    if qdepth is not None:
        model["engine_queue_depth"] = {
            "kind": "gauge", "help": "",
            "series": [{"labels": {}, "value": qdepth}]}
    if group is not None:
        model["engine_last_group_id"] = {
            "kind": "gauge", "help": "",
            "series": [{"labels": {}, "value": group}]}
    return model


def _feed(target, ticks, dt=0.5, per_tick=4, slow=0.05, fast=0.001,
          start=0.0, now0=0.0):
    """Feed ``ticks`` observation passes where rank 1 is the DELAYED
    rank: its own latency is the fleet minimum (``fast``) while rank
    0's inflates by the wait (``slow``) — the arrival-lag inversion.
    Returns the last scores dict."""
    out = {}
    for i in range(1, ticks + 1):
        n = per_tick * i
        models = [("0", ("h0", 0), _model(start + slow * n, n,
                                          qdepth=1, group=n)),
                  ("1", ("h1", 0), _model(start + fast * n, n,
                                          qdepth=0, group=n))]
        out = target.observe(models, now=now0 + dt * i)
    return out


# -- analyzer ---------------------------------------------------------------

def test_analyzer_fingers_the_late_arriver():
    an = skew.SkewAnalyzer(window_secs=2.0)
    scores = _feed(an, ticks=5)
    # Rank 1 dispatches late (everyone waits on it): its own window is
    # the fleet minimum, so ITS score spikes — not the prompt rank's.
    assert scores["1"]["score"] > 10.0, scores
    assert scores["0"]["score"] < 1.0, scores
    assert scores["1"]["queue_depth"] == 0.0
    assert scores["1"]["last_group_id"] == 20.0


def test_analyzer_needs_two_ranks_and_window_data():
    an = skew.SkewAnalyzer(window_secs=2.0)
    # One rank: no median to compare against.
    assert an.observe([("0", None, _model(0.1, 10))], now=0.0) == {}
    assert an.observe([("0", None, _model(0.2, 20))], now=1.0) == {}
    # Two ranks but below MIN_WINDOW_COUNT completions: no scores yet.
    out = an.observe([("0", None, _model(0.21, 21)),
                      ("1", None, _model(0.01, 1))], now=1.5)
    assert "1" not in out


def test_analyzer_drops_departed_ranks():
    an = skew.SkewAnalyzer(window_secs=2.0)
    _feed(an, ticks=3)
    assert an.rank_window("1") is not None
    # Rank 1 left the fleet (drained): its window must reset so a
    # respawn starts a fresh episode.
    an.observe([("0", None, _model(1.0, 20))], now=2.0)
    assert an.rank_window("1") is None


def test_analyzer_falls_back_to_cycle_seconds():
    an = skew.SkewAnalyzer(window_secs=2.0)

    def cyc(lat_sum, count):
        return {"engine_cycle_seconds": {
            "kind": "histogram", "help": "",
            "series": [{"labels": {}, "buckets": {}, "sum": lat_sum,
                        "count": count}]}}

    for i in range(1, 5):
        n = 4 * i
        out = an.observe([("0", None, cyc(0.05 * n, n)),
                          ("1", None, cyc(0.001 * n, n))],
                         now=0.5 * i)
    assert an.source == "engine_cycle_seconds"
    assert out["1"]["score"] > 10.0


# -- env knobs --------------------------------------------------------------

def test_action_env_is_strict(monkeypatch):
    monkeypatch.setenv("HOROVOD_STRAGGLER_ACTION", "Drain")
    assert skew.straggler_action() == "drain"
    monkeypatch.setenv("HOROVOD_STRAGGLER_ACTION", "observe-ish")
    with pytest.raises(ValueError):
        skew.straggler_action()
    monkeypatch.delenv("HOROVOD_STRAGGLER_ACTION")
    assert skew.straggler_action() == "observe"


def test_threshold_and_window_envs(monkeypatch):
    monkeypatch.setenv("HOROVOD_STRAGGLER_THRESHOLD", "0")
    assert skew.straggler_threshold() == 0.0
    monkeypatch.setenv("HOROVOD_STRAGGLER_WINDOW_SECS", "0.01")
    assert skew.straggler_window_secs() == 0.5  # floor
    monkeypatch.setenv("HOROVOD_PLAN_STALENESS_RATIO", "3.5")
    assert skew.plan_staleness_ratio() == 3.5


# -- observatory: sustained detection + actuation ---------------------------

def test_detection_requires_sustained_skew(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS_DIR", str(tmp_path))
    drained = []
    obs = skew.SkewObservatory(threshold=2.0, window_secs=2.0,
                               action="drain",
                               drain_fn=lambda meta: bool(
                                   drained.append(meta)) or True)
    # 3 ticks x 0.5 s: above threshold but not yet sustained 2 s.
    _feed(obs, ticks=3)
    assert drained == []
    assert metrics.series_sum("straggler_detections_total") == 0
    # Scores published from the first complete window regardless.
    assert metrics.gauge("straggler_score", rank="1").value > 10
    # Two more ticks pass the sustained window: exactly one detection,
    # actuated and latched (further ticks must not re-fire).
    _feed(obs, ticks=8)
    assert drained == [("h1", 0)]
    assert metrics.series_sum("straggler_detections_total",
                              rank="1", action="drain") == 1
    _feed(obs, ticks=10)
    assert len(drained) == 1
    events = [r for r in metrics.iter_events(str(tmp_path))
              if r["kind"] == "straggler_detected"]
    assert len(events) == 1
    assert events[0]["rank"] == "1" and events[0]["action"] == "drain"
    assert events[0]["group"] is not None  # timeline correlation


def test_threshold_zero_disables_detection():
    obs = skew.SkewObservatory(threshold=0.0, window_secs=0.5,
                               action="drain",
                               drain_fn=lambda meta: True)
    _feed(obs, ticks=10)
    assert metrics.series_sum("straggler_detections_total") == 0
    # Scores still publish: /skew stays useful with detection off.
    assert metrics.gauge("straggler_score", rank="1").value > 10


def test_shrink_without_scheduler_observes(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS_DIR", str(tmp_path))
    obs = skew.SkewObservatory(threshold=2.0, window_secs=1.0,
                               action="shrink", shrink_fn=None)
    _feed(obs, ticks=8)
    assert obs.describe()["detections"][0]["outcome"] == "observed"


def test_shrink_routes_through_callback_and_can_escalate():
    orders = []
    obs = skew.SkewObservatory(threshold=2.0, window_secs=1.0,
                               action="shrink",
                               shrink_fn=lambda meta: bool(
                                   orders.append(meta)) or True)
    _feed(obs, ticks=8)
    # A shed is a preference, not a guarantee: after a successful
    # shrink the episode RE-ARMS, so a wedged rank that survived the
    # placement change is shed again after another full sustained
    # window (two detections across these 8 half-second ticks).
    assert orders and all(meta == ("h1", 0) for meta in orders)
    assert len(orders) == 2, orders
    assert obs.describe()["detections"][0]["outcome"] == "shrunk"


def test_describe_schema_and_skew_endpoint():
    from horovod_tpu.runner.http_server import RendezvousServer
    obs = skew.SkewObservatory(threshold=2.0, window_secs=2.0,
                               action="observe")
    _feed(obs, ticks=8)
    server = RendezvousServer(secret="sekrit")
    port = server.start()
    try:
        # No provider installed: 404 (this server is a KV first).
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                "http://127.0.0.1:%d/skew" % port, timeout=5)
        server.skew_provider = lambda: json.dumps(obs.describe(),
                                                  default=str)
        # Unauthenticated, like /metrics: read-only telemetry.
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/skew" % port, timeout=5).read()
    finally:
        server.stop()
    doc = json.loads(body)
    assert doc["threshold"] == 2.0
    assert doc["source"] == "mh_collective_seconds"
    assert doc["ranks"]["1"]["score"] > 10
    assert doc["ranks"]["1"]["above_threshold"] is True
    assert doc["detections"][0]["rank"] == "1"
    assert "staleness_ratio" in doc["plan"]


# -- plan-staleness tracking -------------------------------------------------

def test_class_tracker_baseline_then_trip_once():
    tr = skew.ClassLatencyTracker(ratio=2.0, min_count=3)
    key = ("allreduce", "65536")

    def feed(total, count):
        return tr.update({key: (total, count)})

    assert feed(0.004, 4) is None          # first sight
    assert feed(0.008, 8) is None          # baseline = 1 ms
    assert feed(0.012, 12) is None         # healthy
    trip = feed(0.212, 16)                 # 50 ms/op: 50x drift
    assert trip is not None and trip["op"] == "allreduce"
    assert trip["ratio"] > 2.0
    # Re-baselined at the drifted mean: the SAME level cannot re-trip.
    assert feed(0.412, 20) is None
    assert tr.describe()["allreduce/65536"]["stale_trips"] == 1


def test_class_tracker_one_class_per_pass():
    tr = skew.ClassLatencyTracker(ratio=2.0, min_count=2)
    a, b = ("allreduce", "1024"), ("allgather", "4096")
    tr.update({a: (0.002, 2), b: (0.002, 2)})
    tr.update({a: (0.004, 4), b: (0.004, 4)})       # baselines
    trip = tr.update({a: (0.104, 6), b: (0.024, 6)})  # a drifts worse
    assert (trip["op"], trip["size_class"]) == a
    # b's (smaller) drift trips on the NEXT pass — one class at a time.
    trip2 = tr.update({a: (0.204, 8), b: (0.044, 8)})
    assert (trip2["op"], trip2["size_class"]) == b


def test_observatory_plan_staleness_counts_and_journals(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS_DIR", str(tmp_path))
    obs = skew.SkewObservatory(threshold=0.0, window_secs=2.0,
                               action="observe", staleness_ratio=2.0)
    # Healthy fleet (1 ms/op on both ranks), then every rank's class
    # latency drifts 50x — cumulative totals keep growing, as a real
    # pull stream's do.
    total, n = 0.0, 0
    for i in range(1, 13):
        per_op = 0.001 if i <= 6 else 0.05
        n += 4
        total += per_op * 4
        obs.observe([("0", None, _model(total, n)),
                     ("1", None, _model(total, n))], now=0.5 * i)
    # The fleet-view trip journals and shows in /skew; the
    # plan_staleness_total COUNTER belongs to the worker-side
    # actuation alone (check_plan_staleness) — a driver-side bump
    # would double-count one shift against a trip that invalidates
    # nothing.
    assert metrics.series_sum("plan_staleness_total") == 0
    events = [r for r in metrics.iter_events(str(tmp_path))
              if r["kind"] == "plan_stale"]
    assert len(events) == 1 and events[0]["size_class"] == "65536"
    assert events[0]["scope"] == "fleet"
    classes = obs.describe()["plan"]["classes"]
    assert classes["allreduce/65536"]["stale_trips"] == 1


def test_class_tracker_resets_on_total_regression():
    # Fleet-aggregated cumulative totals REGRESS when a member leaves
    # (its lifetime sums drop out of the aggregate): the tracker must
    # start the class over — never freeze until counts regrow, never
    # adopt a clamped 0-mean window as a baseline (the false-trip
    # shape).
    tr = skew.ClassLatencyTracker(ratio=2.0, min_count=3)
    key = ("allreduce", "65536")
    tr.update({key: (0.004, 4)})
    tr.update({key: (0.008, 8)})            # baseline 1 ms
    # A 2x-sized fleet member drained: totals drop below the last
    # sample.  No trip, no frozen window — a clean restart.
    assert tr.update({key: (0.002, 2)}) is None
    rec = tr.describe()["allreduce/65536"]
    assert rec["baseline_s"] is None and rec["stale_trips"] == 0
    # Tracking resumes from the fresh baseline and still detects real
    # drift afterwards.
    assert tr.update({key: (0.006, 6)}) is None   # new baseline 1 ms
    assert tr.update({key: (0.206, 10)}) is not None  # 50 ms: trip


def test_departed_rank_score_gauge_is_removed(tmp_path):
    obs = skew.SkewObservatory(threshold=0.0, window_secs=2.0,
                               action="observe")
    _feed(obs, ticks=5)
    assert metrics.series_sum("straggler_score", rank="1") > 10
    # Rank 1 leaves the fleet (drained): its gauge series must leave
    # the exposition with it, not report its last score forever.
    obs.observe([("0", ("h0", 0), _model(2.0, 40))], now=10.0)
    fam = metrics.snapshot().get("straggler_score", {})
    ranks = {row["labels"].get("rank") for row in fam.get("series", ())}
    assert "1" not in ranks, ranks


# -- plancache actuation -----------------------------------------------------

def _controller_with_entry():
    from horovod_tpu.utils import plancache
    plan = plancache.empty_plan("p2-l1-cpu")
    plan["collectives"] = {"allreduce": {"65536": {
        "path": "hier", "codec": "none"}}}
    return plancache.PlanController("p2-l1-cpu", plan, "cache", "none",
                                    hier_available=True,
                                    env_pinned=False)


def test_plan_controller_invalidate_drops_entry_and_memo():
    ctl = _controller_with_entry()
    assert ctl.route("allreduce", "65536", False) == (True, False)
    assert metrics.series_sum("plan_apply_total", source="cache") == 1
    assert ctl.invalidate("allreduce", "65536") is True
    # Re-resolves by the default gate, recounted with honest source.
    assert ctl.route("allreduce", "65536", False) == (False, True)
    assert metrics.series_sum("plan_apply_total", source="default") == 1
    assert ctl.invalidate("allreduce", "65536") is False  # nothing left


def _local_plane(monkeypatch, size=1, rank=None, kv=None):
    from horovod_tpu.utils import plancache
    plancache.reset()
    p = plancache._plane
    p.enabled = True
    p.fingerprint = "p2-l1-cpu"
    p.size = size
    p.rank = rank
    p.kv = kv
    p.controller = _controller_with_entry()
    return p


def test_check_plan_staleness_local_trips_exactly_once(monkeypatch):
    from horovod_tpu.utils import plancache
    p = _local_plane(monkeypatch)
    h = metrics.histogram("mh_collective_seconds", op="allreduce",
                          size_class="65536")

    def burst(lat, n=4):
        for _ in range(n):
            h.observe(lat)

    burst(0.001)
    assert plancache.check_plan_staleness() is None  # first sight
    burst(0.001)
    assert plancache.check_plan_staleness() is None  # baseline
    burst(0.05)
    v = plancache.check_plan_staleness()             # drift
    assert v is not None and v["size_class"] == "65536"
    assert metrics.series_sum("plan_staleness_total") == 1
    assert plancache.retune_pending() == [("allreduce", "65536")]
    # The cached routing entry is gone on trip.
    assert p.controller.route("allreduce", "65536", False) == (False,
                                                               True)
    burst(0.05)
    assert plancache.check_plan_staleness() is None  # re-baselined
    assert metrics.series_sum("plan_staleness_total") == 1
    assert plancache.consume_retune() == [("allreduce", "65536")]
    assert plancache.retune_pending() == []
    plancache.reset()


def test_check_plan_staleness_multi_without_kv_is_inert(monkeypatch):
    from horovod_tpu.utils import plancache
    _local_plane(monkeypatch, size=2, rank=0, kv=None)
    h = metrics.histogram("mh_collective_seconds", op="allreduce",
                          size_class="65536")
    for _ in range(16):
        h.observe(0.05)
    # Multi-member with no KV: rank-local invalidation would diverge
    # routing — the check must observe NOTHING, uniformly.
    for _ in range(4):
        assert plancache.check_plan_staleness() is None
    assert metrics.series_sum("plan_staleness_total") == 0
    plancache.reset()


def test_check_plan_staleness_member_adopts_rank0_verdict(monkeypatch):
    # The KV half of SPMD uniformity: rank 0 decides and publishes;
    # a member applies the trip at the SAME check index (apply_at),
    # never from its own telemetry (it has none here).
    from horovod_tpu.runner.http_client import RendezvousClient
    from horovod_tpu.runner.http_server import RendezvousServer
    from horovod_tpu.utils import plancache
    server = RendezvousServer(secret="s3")
    server.start()
    try:
        kv = RendezvousClient("127.0.0.1:%d" % server.port, secret="s3")
        # rank 0: trip at its check #3, settle at #4.
        _local_plane(monkeypatch, size=2, rank=0, kv=kv)
        h = metrics.histogram("mh_collective_seconds", op="allreduce",
                              size_class="65536")
        vs = []
        for lat in (0.001, 0.001, 0.05, 0.05):
            for _ in range(4):
                h.observe(lat)
            vs.append(plancache.check_plan_staleness())
        assert vs[:2] == [None, None]
        assert vs[2] is not None and vs[2]["apply_at"] == 3
        assert vs[3] is None  # the settling window must not re-trip
        # member (rank 1): fresh process state, same KV.
        p = _local_plane(monkeypatch, size=2, rank=1, kv=kv)
        metrics.reset()
        assert plancache.check_plan_staleness() is None  # check 1
        assert plancache.check_plan_staleness() is None  # check 2
        v1 = plancache.check_plan_staleness()            # check 3
        assert v1 is not None
        assert (v1["op"], v1["size_class"], v1["apply_at"]) == \
            ("allreduce", "65536", 3)
        assert metrics.series_sum("plan_staleness_total") == 1
        assert plancache.retune_pending() == [("allreduce", "65536")]
        assert p.controller.route("allreduce", "65536", False) == \
            (False, True)
        assert plancache.check_plan_staleness() is None  # check 4
    finally:
        server.stop()
        plancache.reset()


# -- actuation seams ---------------------------------------------------------

class _FakeProc:
    def __init__(self):
        self.terminated = False

    def poll(self):
        return None

    def terminate(self):
        self.terminated = True


def test_driver_straggler_drain_is_planned_removal(monkeypatch):
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.driver import ElasticDriver
    driver = ElasticDriver(["true"], FixedHosts({"h1": 1}), min_np=1,
                           max_np=None)
    slot = ("h1", 0)
    mp = _FakeProc()
    with driver._lock:
        driver._procs[slot] = mp
        driver._spawn_backoff[slot] = 16.0
    assert driver._straggler_drain(slot) is True
    assert mp.terminated  # SIGTERM leads: the r10 drain path
    with driver._lock:
        assert slot in driver._draining        # reap books a drain
        assert slot not in driver._stopped     # the slot respawns
        assert slot not in driver._spawn_backoff  # backoff reset
    # Idempotent: an already-draining slot is not re-terminated.
    assert driver._straggler_drain(slot) is False
    # Unknown slots refuse quietly.
    assert driver._straggler_drain(("h9", 3)) is False


def test_scheduler_shrink_tenant_resizes_and_pokes():
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.scheduler import PodScheduler, TenantSpec

    class _FakeDriver:
        def __init__(self):
            self.bounds = []
            self.scheduler_shrink = None

        def set_np_bounds(self, lo, hi):
            self.bounds.append((lo, hi))

        def run(self):
            time.sleep(30)
            return 0

        def request_stop(self):
            pass

    fakes = {}

    def factory(tenant):
        fakes[tenant.tenant_id] = _FakeDriver()
        return fakes[tenant.tenant_id]

    sched = PodScheduler(FixedHosts({"h1": 3}), tick_secs=3600,
                         driver_factory=factory)
    try:
        sched.admit(TenantSpec("t1", ["true"], min_np=1, max_np=None))
        assert sched.tenant_state("t1") == "running"
        assert sum(sched.allocation("t1").values()) == 3
        # Shrink sheds ONE slot: max_np lands at allocated-1 and the
        # bound propagates to the live driver (resize + poke).
        assert sched.shrink_tenant("t1") is True
        assert fakes["t1"].bounds[-1] == (1, 2)
        sched.tick()
        assert sum(sched.allocation("t1").values()) == 2
        # At the min_np floor the shrink is refused.
        sched.resize("t1", max_np=1)
        sched.tick()
        assert sched.shrink_tenant("t1") is False
        # Unknown tenants refuse quietly.
        assert sched.shrink_tenant("nope") is False
    finally:
        sched.stop(timeout=2.0)


def test_scheduler_shrink_sheds_the_straggler_host():
    # The shed must land on the STRAGGLER's host, not an arbitrary
    # slot: shrink_tenant(host=...) records an avoid-host preference
    # the packer honors (that host fills LAST), so the tightened
    # max_np drops its slot.
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.scheduler import PodScheduler, TenantSpec

    class _FakeDriver:
        scheduler_shrink = None

        def set_np_bounds(self, lo, hi):
            pass

        def run(self):
            time.sleep(30)
            return 0

        def request_stop(self):
            pass

    sched = PodScheduler(FixedHosts({"h1": 2, "h2": 1}), tick_secs=3600,
                         driver_factory=lambda t: _FakeDriver())
    try:
        sched.admit(TenantSpec("t1", ["true"], min_np=1, max_np=None))
        assert sched.allocation("t1") == {"h1": 2, "h2": 1}
        # Straggler detected on h2: the shed must take h2's slot even
        # though host order would otherwise trim from the tail of h1.
        assert sched.shrink_tenant("t1", host="h2") is True
        sched.tick()
        assert sched.allocation("t1") == {"h1": 2}
    finally:
        sched.stop(timeout=2.0)


def test_scheduler_wires_shrink_hook_onto_tenant_drivers():
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.elastic.scheduler import (PodScheduler, TenantSpec,
                                               _Tenant)
    sched = PodScheduler(FixedHosts({"h1": 2}), tick_secs=3600)
    tenant = _Tenant(TenantSpec("t1", ["true"], min_np=1), 0)
    tenant.view.set({"h1": 2})
    with sched._lock:
        sched._tenants["t1"] = tenant
    driver = sched._make_driver(tenant)
    try:
        assert driver.scheduler_shrink is not None
        # The hook IS the observatory's shrink actuation path: one
        # call sheds one slot of this tenant's share.
        assert driver._straggler_shrink(("h1", 0)) is True
        assert tenant.spec.max_np == 1
    finally:
        driver.request_stop()


# -- e2e: detection -> drain -> recovery (slow; CI by node id) ---------------

@pytest.mark.slow
def test_straggler_detection_drain_recovery_e2e(tmp_path):
    """The whole loop on a real elastic multihost world: a dispatch-
    seam delay wedges one host (epoch 1 only), the driver's skew loop
    detects the sustained arrival lag, drains the straggler as a
    planned removal (no blacklist), and the re-formed world — with the
    straggler's healthy epoch-2 respawn — finishes every batch."""
    events_dir = tmp_path / "events"
    script = tmp_path / "train.py"
    script.write_text("""
import os, sys, time
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()
state = elastic.ObjectState(batch=0)

@elastic.run
def train(state):
    while state.batch < 40:
        hvd.allreduce(np.ones(256, np.float32), op=hvd.Sum,
                      name="b%d" % state.batch)
        state.batch += 1
        state.commit()
    print("DONE rank=%d size=%d batch=%d"
          % (hvd.rank(), hvd.size(), state.batch), flush=True)

train(state)
""")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HOROVOD_RANK", None)
    env.pop("HOROVOD_ELASTIC_DRIVER_ADDR", None)
    env.update({
        "HVD_TPU_FAULT":
            "mh.drain.record:delay:0.15@host=127.0.0.2@epoch=1",
        "HOROVOD_METRICS_DIR": str(events_dir),
        "HOROVOD_STRAGGLER_THRESHOLD": "2",
        "HOROVOD_STRAGGLER_WINDOW_SECS": "2",
        "HOROVOD_STRAGGLER_ACTION": "drain",
        # A real drain window (ManagedProcess's default 5 s escalation
        # can SIGKILL the straggler mid-teardown otherwise).
        "HOROVOD_PREEMPT_GRACE_SECS": "20",
    })
    proc = run_world(
        [sys.executable, "-m", "horovod_tpu.runner", "--multihost",
         "-H", "127.0.0.1:1,127.0.0.2:1", "--min-np", "1",
         "--max-np", "2",
         sys.executable, str(script)],
        timeout=300,
        env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # Every batch finished; the straggler's respawn recovered too.
    assert "DONE rank=0" in proc.stdout, proc.stdout
    # Detection fired and actuated as a drain (driver journal).
    kinds = {}
    detection = None
    for rec in metrics.iter_events(str(events_dir), merged=True):
        kinds[rec["kind"]] = kinds.get(rec["kind"], 0) + 1
        if rec["kind"] == "straggler_detected" and detection is None:
            detection = rec
    assert detection is not None, kinds
    assert detection["action"] == "drain"
    assert float(detection["score"]) >= 2.0
    assert kinds.get("straggler_drain_order"), kinds
    assert kinds.get("drained"), kinds
    # Planned removal, not a failure: no blacklist anywhere.
    assert "blacklisting host" not in proc.stderr, proc.stderr
    assert not kinds.get("blacklist"), kinds
