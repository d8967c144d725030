"""Collective-plan cache tests (utils/plancache.py): blob codec +
atomic persistence, loud fallbacks for corrupt/mismatched blobs, KV
publish/adopt, per-class routing precedence (env wins and suppresses
pinning), the PlanTuner GP sweep unit,
the crash-safe AutotuneLog writer, and the slow-marked cold-vs-warm
2-proc e2e the CI perf-smoke step runs by node id."""

import json
import logging
import os
import threading
import types

import pytest

from horovod_tpu.common import metrics
from horovod_tpu.common.config import Config
from horovod_tpu.utils import plancache
from horovod_tpu.utils.autotune import (AutotuneLog, ParameterManager,
                                        PlanTuner)

FP = plancache.topology_fingerprint(2, 4, "TPU v5e")


@pytest.fixture(autouse=True)
def _fresh_planes():
    metrics.reset()
    plancache.reset()
    yield
    metrics.reset()
    plancache.reset()


def _plan(fingerprint=FP):
    plan = plancache.empty_plan(fingerprint)
    plan["tuned"] = {"fusion_threshold": 1 << 25,
                     "cycle_time_ms": 3.5, "converged": True}
    plan["collectives"] = {
        "allreduce": {"20": {"path": "hier", "codec": "int8"},
                      "12": {"path": "flat", "codec": "none"}}}
    return plan


# -- blob codec + on-disk roundtrip ----------------------------------------

def test_roundtrip_and_hit_counter(tmp_path):
    plan = _plan()
    path = plancache.store(plan, str(tmp_path))
    assert path and os.path.exists(path)
    assert plancache.load(str(tmp_path), FP) == plan
    assert metrics.series_sum("plan_cache_hits_total") == 1
    assert metrics.series_sum("plan_cache_misses_total") == 0


def test_absent_blob_is_a_miss(tmp_path):
    assert plancache.load(str(tmp_path), FP) is None
    assert metrics.series_sum("plan_cache_misses_total") == 1


def test_corrupt_crc_falls_back_loudly(tmp_path, caplog):
    path = plancache.store(_plan(), str(tmp_path))
    with open(path, "r+b") as f:
        f.seek(-3, os.SEEK_END)
        f.write(b"XXX")
    with caplog.at_level(logging.WARNING, "horovod_tpu.plancache"):
        assert plancache.load(str(tmp_path), FP) is None
    assert "ignoring unusable plan cache" in caplog.text
    assert metrics.series_sum("plan_cache_misses_total") == 1
    assert metrics.series_sum("plan_cache_hits_total") == 0


def test_version_mismatch_falls_back_loudly(tmp_path, caplog):
    blob = plancache.encode(_plan())
    head = plancache._HEADER.unpack(
        blob[len(plancache.MAGIC):
             len(plancache.MAGIC) + plancache._HEADER.size])
    bad = (plancache.MAGIC
           + plancache._HEADER.pack(plancache.SCHEMA_VERSION + 1,
                                    *head[1:])
           + blob[len(plancache.MAGIC) + plancache._HEADER.size:])
    with pytest.raises(plancache.PlanCacheInvalid, match="schema"):
        plancache.decode(bad)
    path = plancache.plan_path(str(tmp_path), FP)
    os.makedirs(str(tmp_path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(bad)
    with caplog.at_level(logging.WARNING, "horovod_tpu.plancache"):
        assert plancache.load(str(tmp_path), FP) is None
    assert "falling back to default plans" in caplog.text


def test_torn_payload_and_bad_magic_rejected():
    blob = plancache.encode(_plan())
    with pytest.raises(plancache.PlanCacheInvalid, match="torn"):
        plancache.decode(blob[:-4])
    with pytest.raises(plancache.PlanCacheInvalid, match="magic"):
        plancache.decode(b"NOTAPLAN" + blob)
    with pytest.raises(plancache.PlanCacheInvalid, match="magic"):
        plancache.decode(b"")


def test_fingerprint_mismatch_is_a_loud_miss(tmp_path, caplog):
    other = plancache.topology_fingerprint(8, 4, "TPU v4")
    plan = _plan(other)
    # Land the wrong-fingerprint blob at THIS fingerprint's path (a
    # copied cache dir from another pod shape).
    blob_path = plancache.plan_path(str(tmp_path), FP)
    os.makedirs(str(tmp_path), exist_ok=True)
    with open(blob_path, "wb") as f:
        f.write(plancache.encode(plan))
    with caplog.at_level(logging.WARNING, "horovod_tpu.plancache"):
        assert plancache.load(str(tmp_path), FP) is None
    assert "claims fingerprint" in caplog.text
    assert metrics.series_sum("plan_cache_misses_total") == 1


def test_concurrent_writers_always_leave_a_complete_blob(tmp_path):
    # N threads store distinct plans concurrently; every intermediate
    # and final state of the cache file must decode (tmp + os.replace:
    # last complete blob wins, readers never see a torn write).
    plans = []
    for i in range(8):
        p = _plan()
        p["tuned"]["fusion_threshold"] = 1 << (20 + i)
        plans.append(p)
    errs = []

    def write(p):
        try:
            assert plancache.store(p, str(tmp_path))
        except Exception as exc:  # noqa: BLE001 - collected for assert
            errs.append(exc)

    threads = [threading.Thread(target=write, args=(p,)) for p in plans]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    loaded = plancache.load(str(tmp_path), FP)
    assert loaded in plans  # one complete winner, never a mix
    leftovers = [f for f in os.listdir(str(tmp_path))
                 if f.startswith(".tmp-plan-")]
    assert leftovers == []


def test_store_into_unwritable_dir_degrades(tmp_path, caplog):
    # A regular file where the cache dir should be: makedirs fails with
    # an OSError on every platform (chmod tricks don't bind root).
    blocker = tmp_path / "blocker"
    blocker.write_text("")
    target = str(blocker / "cache")
    with caplog.at_level(logging.WARNING, "horovod_tpu.plancache"):
        assert plancache.store(_plan(), target) is None
    assert "plan-cache write" in caplog.text


def test_topology_fingerprint_sanitizes_device_kind():
    assert plancache.topology_fingerprint(2, 4, "TPU v5 lite/pod") == \
        "p2-l4-TPU_v5_lite_pod"
    assert plancache.topology_fingerprint(1, 1, "") == "p1-l1-unknown"


# -- fleet sharing over the rendezvous KV ----------------------------------

class _FakeKV:
    def __init__(self):
        self.store = {}
        self.put_fail = False

    def put_json(self, key, obj):
        if self.put_fail:
            raise OSError("kv down")
        self.store[key] = json.dumps(obj, sort_keys=True)

    def get_json(self, key):
        v = self.store.get(key)
        return json.loads(v) if v is not None else None

    def get_blocking(self, key, timeout=60.0):
        if key not in self.store:
            raise TimeoutError("no key %s" % key)
        return self.store[key]


def test_kv_publish_then_adopt_roundtrip():
    kv = _FakeKV()
    plan = _plan()
    plancache.publish_kv(kv, plan)
    assert plancache.adopt_kv(kv, FP, timeout=0.1) == plan


def test_kv_adopt_timeout_and_torn_blob_degrade(caplog):
    kv = _FakeKV()
    with caplog.at_level(logging.WARNING, "horovod_tpu.plancache"):
        assert plancache.adopt_kv(kv, FP, timeout=0.05) is None
    assert "using default plans" in caplog.text
    # A published blob for the WRONG fingerprint must not be adopted.
    other = plancache.topology_fingerprint(9, 9, "x")
    kv.store[plancache._KV_KEY % (plancache.SCHEMA_VERSION, FP)] = \
        json.dumps(_plan(other))
    assert plancache.adopt_kv(kv, FP, timeout=0.05) is None


def test_kv_publish_failure_never_raises(caplog):
    kv = _FakeKV()
    kv.put_fail = True
    with caplog.at_level(logging.WARNING, "horovod_tpu.plancache"):
        plancache.publish_kv(kv, _plan())  # must not raise
    assert "plan KV publish failed" in caplog.text


# -- per-class routing controller ------------------------------------------

def _controller(env_pinned=False, codec="int8", hier_available=True,
                plan=None):
    return plancache.PlanController(
        FP, plan if plan is not None else _plan(), "cache", codec,
        hier_available=hier_available, env_pinned=env_pinned)


def test_route_precedence_cache_then_default():
    ctl = _controller()
    # Cached class: the plan's decision wins over the gate's answer.
    assert ctl.route("allreduce", "20", False) == (True, True)
    assert ctl.route("allreduce", "12", True) == (False, False)
    # Unknown class: fall back to the global gate's answer.
    assert ctl.route("allreduce", "27", True) == (True, True)
    assert ctl.route("allreduce", "8", False) == (False, True)
    # Counted once per (op, size_class) resolution: two cached
    # classes, two default classes.
    assert metrics.series_sum("plan_apply_total", source="cache") == 2
    assert metrics.series_sum("plan_apply_total", source="default") == 2
    # Re-routing an already-counted class does not double count.
    ctl.route("allreduce", "20", False)
    assert metrics.series_sum("plan_apply_total", source="cache") == 2


def test_route_pin_wins_over_cached_plan():
    ctl = _controller()
    assert ctl.pin("allreduce", "20", {"path": "flat", "codec": "none"})
    assert ctl.route("allreduce", "20", True) == (False, False)
    assert metrics.series_sum("plan_apply_total", source="tuned") == 1
    table = ctl.decisions()
    assert table["allreduce"]["20"] == {
        "path": "flat", "codec": "none", "source": "tuned"}


def test_env_pins_suppress_plan_and_pinning():
    # An explicit operator gate env
    # wins over any persisted plan AND refuses tuner pinning.
    ctl = _controller(env_pinned=True)
    assert ctl.route("allreduce", "12", True) == (True, True)
    assert ctl.route("allreduce", "20", False) == (False, True)
    assert ctl.pin("allreduce", "20",
                   {"path": "hier", "codec": "int8"}) is False
    assert metrics.series_sum("plan_apply_total", source="default") == 2
    assert metrics.series_sum("plan_apply_total", source="cache") == 0


def test_invalidate_under_concurrent_route_hammer():
    # ISSUE 18: the resilience demotion path calls invalidate() + pin()
    # from the SPMD check while dispatch threads race route() on the
    # same class (the memoized lock-free fast path).  No resolution may
    # tear (every answer is a well-formed pair from SOME consistent
    # state) and the settled answer must match the last verdict.
    ctl = _controller()
    stop = threading.Event()
    errors = []

    def dispatcher():
        while not stop.is_set():
            try:
                hier, codec_on = ctl.route("allreduce", "20", True)
                # flat pin -> (False, False); re-resolved cached entry
                # or default -> hier with codec.  Nothing else exists.
                assert (hier, codec_on) in ((False, False),
                                            (True, True)), \
                    (hier, codec_on)
            except Exception as exc:  # noqa: BLE001 — the assertion
                errors.append(exc)
                return

    threads = [threading.Thread(target=dispatcher) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            # demote: drop the entry, pin flat (the _apply_route pair)
            ctl.invalidate("allreduce", "20")
            ctl.pin("allreduce", "20", {"path": "flat", "codec": "none"})
            # promote: invalidate drops the pin, route re-resolves
            ctl.invalidate("allreduce", "20")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors[0]
    assert ctl.route("allreduce", "20", True) == (True, True)
    ctl.invalidate("allreduce", "20")
    ctl.pin("allreduce", "20", {"path": "flat", "codec": "none"})
    assert ctl.route("allreduce", "20", True) == (False, False)


def test_route_hier_unavailable_world_never_routes_hier():
    ctl = _controller(hier_available=False)
    use_hier, _ = ctl.route("allreduce", "20", False)
    assert use_hier is False


def test_codec_engagement_requires_matching_world_codec():
    # Plan says int8 but this world runs uncompressed: the cached path
    # choice survives, the codec engagement does not.
    ctl = _controller(codec="none")
    assert ctl.route("allreduce", "20", False) == (True, False)


def test_force_overrides_every_class_until_cleared():
    ctl = _controller()
    ctl.force({"path": "flat", "codec": "none"})
    assert ctl.route("allreduce", "20", True) == (False, False)
    ctl.force({"path": "hier", "codec": "int8"})
    assert ctl.route("allreduce", "12", False) == (True, True)
    ctl.force(None)
    assert ctl.route("allreduce", "20", False) == (True, True)
    assert ctl.last_class("allreduce") == "20"


def test_export_collectives_merges_seen_and_pinned():
    ctl = _controller()
    ctl.route("allreduce", "20", False)
    ctl.pin("broadcast", "16", {"path": "hier", "codec": "none"})
    exported = ctl.export_collectives()
    assert exported["allreduce"]["20"] == {"path": "hier",
                                           "codec": "int8"}
    assert exported["broadcast"]["16"] == {"path": "hier",
                                           "codec": "none"}
    assert "source" not in exported["allreduce"]["20"]


# -- bootstrap / finalize lifecycle ----------------------------------------

def _topo(rank=0, size=1):
    return types.SimpleNamespace(rank=rank, size=size)


def test_bootstrap_applies_tuned_point_and_counts(tmp_path, monkeypatch):
    for var in ("HVD_TPU_FUSION_THRESHOLD", "HOROVOD_FUSION_THRESHOLD",
                "HVD_TPU_CYCLE_TIME", "HOROVOD_CYCLE_TIME"):
        monkeypatch.delenv(var, raising=False)
    plancache.store(_plan(plancache.topology_fingerprint(1, 1, "host")),
                    str(tmp_path))
    cfg = Config(plan_cache_dir=str(tmp_path))
    plan = plancache.bootstrap(cfg, _topo(), mode="tcp")
    assert plan is not None
    assert cfg.fusion_threshold_bytes == 1 << 25
    assert cfg.cycle_time_ms == 3.5
    assert plancache.tuned_warm_start() == (1 << 25, 3.5, True)
    assert metrics.series_sum("plan_cache_hits_total") == 1
    assert metrics.series_sum("plan_apply_total", source="cache") == 1


def test_bootstrap_env_wins_over_tuned_point(tmp_path, monkeypatch):
    plancache.store(_plan(plancache.topology_fingerprint(1, 1, "host")),
                    str(tmp_path))
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", str(1 << 26))
    cfg = Config(plan_cache_dir=str(tmp_path),
                 fusion_threshold_bytes=1 << 26)
    plancache.bootstrap(cfg, _topo(), mode="tcp")
    assert cfg.fusion_threshold_bytes == 1 << 26  # env untouched
    assert plancache.tuned_warm_start() is None   # warm start suppressed


def test_bootstrap_disabled_or_dirless_is_inert(tmp_path):
    assert plancache.bootstrap(Config(), _topo(), mode="tcp") is None
    assert plancache.tuned_warm_start() is None
    cfg = Config(plan_cache=False, plan_cache_dir=str(tmp_path))
    assert plancache.bootstrap(cfg, _topo(), mode="tcp") is None


def test_bootstrap_multihost_without_kv_drops_local_plan(
        tmp_path, caplog, monkeypatch):
    """Regression for the spmd-uniform finding: a multihost world with
    a plan dir but NO rendezvous KV used to apply each host's local
    cache blob to routing — per-host files can differ (independent
    disks, one stale rerun), which is the r14 divergent-routing hang
    class.  The blob must be dropped, loudly."""
    for var in ("HVD_TPU_FUSION_THRESHOLD", "HOROVOD_FUSION_THRESHOLD",
                "HVD_TPU_CYCLE_TIME", "HOROVOD_CYCLE_TIME"):
        monkeypatch.delenv(var, raising=False)
    # Resolve the fingerprint exactly as bootstrap will for this host.
    plancache.bootstrap(Config(plan_cache_dir=str(tmp_path)),
                        _topo(size=4), mode="multihost")
    fp = plancache._plane.fingerprint
    plancache.reset()
    plancache.store(_plan(fp), str(tmp_path))
    cfg = Config(plan_cache_dir=str(tmp_path))
    defaults = (cfg.fusion_threshold_bytes, cfg.cycle_time_ms)
    with caplog.at_level(logging.WARNING):
        plan = plancache.bootstrap(cfg, _topo(rank=1, size=4),
                                   mode="multihost")
    assert plan is not None and not plancache.plan_has_content(plan)
    assert plancache.tuned_warm_start() is None
    assert (cfg.fusion_threshold_bytes, cfg.cycle_time_ms) == defaults
    assert "no rendezvous KV" in caplog.text
    # The controller exists but routes by the EMPTY (agreed) plan.
    ctl = plancache._plane.controller
    assert ctl is not None and ctl.route("allreduce", "20", True)[0] \
        is True
    plancache.reset()
    # tcp mode keeps its local view: no routing controller to diverge,
    # fusion/cycle pacing is per-process by design there.
    cfg_tcp = Config(plan_cache_dir=str(tmp_path))
    plancache.store(_plan(plancache.topology_fingerprint(4, 1, "host")),
                    str(tmp_path))
    plancache.bootstrap(cfg_tcp, _topo(rank=1, size=4), mode="tcp")
    assert plancache.tuned_warm_start() is not None


def test_finalize_persists_inprocess_tuner_point(tmp_path):
    cfg = Config(plan_cache_dir=str(tmp_path))
    plancache.bootstrap(cfg, _topo(), mode="tcp")
    pm = ParameterManager(1 << 23, 7.0)
    pm.frozen = True
    pm._samples_done = 5  # converged by live tuning this run
    engine = types.SimpleNamespace(parameter_manager=pm)
    plancache.finalize(tcp_core=None, engine=engine)
    loaded = plancache.load(
        str(tmp_path), plancache.topology_fingerprint(1, 1, "host"))
    assert loaded is not None
    assert loaded["tuned"] == {"fusion_threshold": 1 << 23,
                               "cycle_time_ms": 7.0, "converged": True}
    assert metrics.series_sum("plan_apply_total", source="tuned") == 1


def test_finalize_warm_started_frozen_pm_is_not_restamped_as_tuned(
        tmp_path):
    # A PM born frozen from a cache warm start sampled nothing: its
    # point is cached provenance, and finalize must not re-stage it as
    # "tuned" (that would corrupt plan_apply_total's provenance and
    # bench attribution).  The loaded plan still persists unchanged
    # through the merge.
    fp = plancache.topology_fingerprint(1, 1, "host")
    plancache.store(_plan(fp), str(tmp_path))
    cfg = Config(plan_cache_dir=str(tmp_path))
    plancache.bootstrap(cfg, _topo(), mode="tcp")
    pm = ParameterManager(1 << 26, 5.0,
                          warm_start=plancache.tuned_warm_start())
    assert pm.frozen and pm.samples_done == 0
    engine = types.SimpleNamespace(parameter_manager=pm)
    plancache.finalize(tcp_core=None, engine=engine)
    assert metrics.series_sum("plan_apply_total", source="tuned") == 0
    loaded = plancache.load(str(tmp_path), fp)
    assert loaded["tuned"]["fusion_threshold"] == 1 << 25  # unchanged


def test_finalize_without_content_writes_nothing(tmp_path):
    cfg = Config(plan_cache_dir=str(tmp_path))
    plancache.bootstrap(cfg, _topo(), mode="tcp")
    plancache.finalize(tcp_core=None, engine=None)
    assert [f for f in os.listdir(str(tmp_path))
            if f.endswith(plancache._SUFFIX)] == []


def test_describe_reports_levers_plan_schema(tmp_path):
    cfg = Config(plan_cache_dir=str(tmp_path))
    plancache.bootstrap(cfg, _topo(), mode="tcp")
    out = plancache.describe()
    assert out["enabled"] is True
    assert out["schema"] == plancache.SCHEMA_VERSION
    assert out["dir"] == str(tmp_path)
    assert out["fingerprint"] == plancache.topology_fingerprint(
        1, 1, "host")
    assert set(out["apply"]) == {"cache", "kv", "tuned", "default"}


# -- PlanTuner (GP/EI over the candidate plan grid) ------------------------

def test_plan_tuner_bootstraps_every_candidate_once():
    t = PlanTuner([(0.0, 0.0), (1.0, 0.0), (1.0, 1.0)])
    seen = []
    for _ in range(3):
        i = t.propose()
        t.record(i, 1.0)
        seen.append(i)
    assert sorted(seen) == [0, 1, 2]


def test_plan_tuner_converges_to_best_mean():
    scores = [1.0, 3.0, 2.0]
    t = PlanTuner([(0.0, 0.0), (1.0, 0.0), (1.0, 1.0)], max_samples=8)
    while not t.converged:
        i = t.propose()
        t.record(i, scores[i])
    assert t.best() == 1
    means = t.mean_scores()
    assert means[1] == 3.0


def test_plan_tuner_single_candidate_and_bad_index():
    t = PlanTuner([(0.0, 0.0)])
    assert not t.converged
    t.record(t.propose(), 5.0)
    assert t.converged and t.best() == 0
    with pytest.raises(IndexError):
        t.record(7, 1.0)
    with pytest.raises(ValueError):
        PlanTuner([])


# -- crash-safe autotune log (the satellite bugfix) ------------------------

def test_autotune_log_rank_stamped_and_append(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_RANK", "3")
    base = str(tmp_path / "at.csv")
    log = AutotuneLog(base)
    assert log.path == base + ".r3"
    log.write_line("1,2,3.0,4.0")
    log.close()
    # Reopen: appends, header not restamped.
    log2 = AutotuneLog(base)
    log2.write_line("2,3,4.0,5.0")
    log2.close()
    lines = open(base + ".r3").read().splitlines()
    assert lines == [AutotuneLog.HEADER, "1,2,3.0,4.0", "2,3,4.0,5.0"]


def test_autotune_log_pid_fallback_and_bad_path(tmp_path, monkeypatch):
    monkeypatch.delenv("HOROVOD_RANK", raising=False)
    log = AutotuneLog(str(tmp_path / "at.csv"))
    assert log.path.endswith(".pid%d" % os.getpid())
    log.close()
    # Unwritable path: degrade to a no-op writer, never raise.
    bad = AutotuneLog(str(tmp_path / "no" / "such" / "dir" / "x.csv"))
    bad.write_line("ignored")
    bad.close()


def test_parameter_manager_writes_through_autotune_log(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv("HOROVOD_RANK", "0")
    base = str(tmp_path / "pm.csv")
    pm = ParameterManager(1 << 26, 5.0, log_path=base, warmup=0,
                          steps_per_sample=1, max_samples=2)
    for _ in range(8):
        pm.observe(1 << 20, 0.001)
    del pm  # close is implicit via fd lifetime; file already flushed
    content = open(base + ".r0").read()
    assert content.startswith(AutotuneLog.HEADER)
    assert "# converged:" in content


def test_parameter_manager_warm_start_skips_warmup_and_freezes():
    pm = ParameterManager(1 << 26, 5.0, warmup=3,
                          warm_start=(1 << 24, 2.0, True))
    assert pm.fusion_threshold == 1 << 24
    assert pm.cycle_time_ms == 2.0
    assert pm.warmup == 0 and pm.frozen
    before = (pm.fusion_threshold, pm.cycle_time_ms)
    for _ in range(50):
        pm.observe(1 << 20, 0.001)
    assert (pm.fusion_threshold, pm.cycle_time_ms) == before
    assert pm.samples_done == 0


def test_parameter_manager_unconverged_warm_start_keeps_sampling():
    pm = ParameterManager(1 << 26, 5.0, warmup=3, steps_per_sample=1,
                          max_samples=30,
                          warm_start=(1 << 24, 2.0, False))
    # Unconverged: ONE warm-up cycle survives (the rerun's first
    # observation is compile-skewed and must not enter the GP), then
    # sampling resumes — still strictly fewer warm-ups than cold (3).
    assert pm.warmup == 1 and not pm.frozen
    # The adopted operating point stays live through the warm-up.
    pm.observe(1 << 20, 0.001)
    assert pm.fusion_threshold == 1 << 24
    for _ in range(4):
        pm.observe(1 << 20, 0.001)
    assert pm.samples_done > 0  # tuning resumed after the warm-up


def test_route_memo_invalidated_by_pin():
    ctl = _controller()
    assert ctl.route("allreduce", "27", True) == (True, True)  # default
    # Memoized fast path returns the same resolution...
    assert ctl.route("allreduce", "27", True) == (True, True)
    # ...until a pin changes it.
    ctl.pin("allreduce", "27", {"path": "flat", "codec": "none"})
    assert ctl.route("allreduce", "27", True) == (False, False)


# -- KV-bootstrapped worlds (fake client via monkeypatch) ------------------

class _FakeRendezvous(_FakeKV):
    calls = []

    def __init__(self, addr, secret=None):
        super().__init__()
        self.store = _FakeRendezvous.shared
        _FakeRendezvous.calls.append(addr)


def _kv_world(monkeypatch, shared=None):
    from horovod_tpu.runner import http_client
    _FakeRendezvous.shared = shared if shared is not None else {}
    _FakeRendezvous.calls = []
    monkeypatch.setattr(http_client, "RendezvousClient",
                        _FakeRendezvous)
    return _FakeRendezvous.shared


def test_bootstrap_kv_only_plane_without_cache_dir(monkeypatch):
    # Ephemeral-disk pods: no HOROVOD_PLAN_CACHE_DIR, but a rendezvous
    # KV — the plane stays live for fleet sharing (rank 0 publishes,
    # members adopt) instead of silently disabling.
    shared = _kv_world(monkeypatch)
    cfg = Config(rendezvous_addr="127.0.0.1:1")
    plan = plancache.bootstrap(cfg, _topo(rank=0, size=2), mode="tcp")
    assert plan is not None and plancache._plane.enabled
    key = plancache._KV_KEY % (plancache.SCHEMA_VERSION,
                               plancache._plane.fingerprint)
    assert key in shared  # rank 0 published (an empty plan is an answer)
    # finalize with live-tuned state republishes without a dir.
    pm = ParameterManager(1 << 23, 7.0)
    pm.frozen = True
    pm._samples_done = 5
    plancache.finalize(
        tcp_core=None, engine=types.SimpleNamespace(parameter_manager=pm))
    assert json.loads(shared[key])["tuned"]["fusion_threshold"] == 1 << 23


def test_bootstrap_kv_only_rank0_adopts_prior_instead_of_clobbering(
        monkeypatch):
    # Cross-run KV-only warm start: run 1's shutdown republished a
    # tuned plan; run 2's rank 0 (no cache dir, nothing local) must
    # adopt that prior answer — not clobber the key with empty_plan()
    # and force the fleet to re-tune every run.
    shared = _kv_world(monkeypatch)
    fp = plancache.topology_fingerprint(2, 1, "host")  # size-2 world
    key = plancache._KV_KEY % (plancache.SCHEMA_VERSION, fp)
    shared[key] = json.dumps(_plan(fp), sort_keys=True)
    cfg = Config(rendezvous_addr="127.0.0.1:1")
    plancache.bootstrap(cfg, _topo(rank=0, size=2), mode="tcp")
    assert plancache._plane.fingerprint == fp
    assert plancache._plane.source == "kv"
    assert plancache.tuned_warm_start() == (1 << 25, 3.5, True)
    assert json.loads(shared[key])["tuned"]["fusion_threshold"] == \
        1 << 25  # republished content unchanged (idempotent publish)


def test_bootstrap_member_adopts_rank0_answer_even_when_empty(
        monkeypatch, tmp_path):
    # Member has a contentful LOCAL blob but rank 0 published "no
    # plan": the member must agree with rank 0 (divergent routing
    # diverges negotiated programs), so the empty answer wins.
    fp = plancache.topology_fingerprint(2, 1, "host")  # size-2 world
    plancache.store(_plan(fp), str(tmp_path))
    shared = _kv_world(monkeypatch)
    shared[plancache._KV_KEY % (plancache.SCHEMA_VERSION, fp)] = \
        json.dumps(plancache.empty_plan(fp))
    cfg = Config(plan_cache_dir=str(tmp_path),
                 rendezvous_addr="127.0.0.1:1")
    plancache.bootstrap(cfg, _topo(rank=1, size=2), mode="tcp")
    assert plancache._plane.fingerprint == fp
    # The local blob WAS loaded (a hit) but the adopted empty answer
    # replaced it.
    assert metrics.series_sum("plan_cache_hits_total") == 1
    assert plancache.tuned_warm_start() is None  # local blob not used
    assert plancache._plane.source is None


def test_bootstrap_multihost_member_fails_loudly_on_adopt_failure(
        monkeypatch):
    # Empty KV (rank 0 never published / timed out): a multihost
    # member must not guess — default-gate routing against rank 0's
    # planned routing hangs the world, so init fails loudly instead.
    _kv_world(monkeypatch)
    cfg = Config(rendezvous_addr="127.0.0.1:1")
    with pytest.raises(RuntimeError, match="KV adoption failed"):
        plancache.bootstrap(cfg, _topo(rank=1, size=2),
                            mode="multihost")
    # The same failure on a tcp world (no routing controller) only
    # degrades to the local view.
    plancache.reset()
    plan = plancache.bootstrap(cfg, _topo(rank=1, size=2), mode="tcp")
    assert plan is not None


# -- cold-vs-warm 2-proc e2e (the CI perf-smoke scenario) ------------------

@pytest.mark.slow
def test_warm_cache_run_skips_retuning_2proc(tmp_path):
    """Run a real 2-proc tcp world twice against one shared
    HOROVOD_PLAN_CACHE_DIR: the cold run tunes and persists, the warm
    run must report ``plan_cache_hits_total`` > 0 and
    ``plan_apply_total{source="cache"}`` > 0 and skip warm-up sampling
    (asserted in-worker, where the counters live)."""
    from tests.utils.spawn import spawn_world

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "utils", "plan_warm_worker.py")
    env = {
        "HOROVOD_PLAN_CACHE_DIR": str(tmp_path),
        "HOROVOD_PLAN_CACHE": "1",
        "HOROVOD_AUTOTUNE": "1",
        # Fast native-tuner pacing: 1 warm-up cycle, 1 cycle/sample,
        # so 60 steady allreduces clear the 25-sample grid walk.
        "HVD_TPU_AUTOTUNE_WARMUP_CYCLES": "1",
        "HVD_TPU_AUTOTUNE_CYCLES_PER_SAMPLE": "1",
    }
    for phase in ("cold", "warm"):
        env["PLAN_PHASE"] = phase
        # pop_env: the warm start only engages on a default-config
        # rerun (an explicit cycle-time env wins over the tuned
        # point), so neither the harness pin nor an inherited operator
        # env may reach the workers.
        results = spawn_world(worker, 2, extra_env=dict(env),
                              timeout=180,
                              pop_env=("HOROVOD_CYCLE_TIME",
                                       "HVD_TPU_CYCLE_TIME"))
        for rank, (rc, out, err) in enumerate(results):
            assert rc == 0, "%s rank %d failed:\n%s\n%s" % (
                phase, rank, out, err)
            assert ("PLAN_%s_OK" % phase.upper()) in out


# -- plans written when the flash blocks were tuned -------------------------

def test_an_old_plan_with_flash_blocks_loads_and_moves_no_block(tmp_path):
    """A plan file from before the flash blocks came from the call's shape
    alone holds ``flash_blocks``: it loads, its tuned point applies, and no
    flash call's blocks move."""
    from horovod_tpu.ops import pallas_kernels as pk
    before = pk._plan(512, 128)
    plan = _plan(plancache.topology_fingerprint(1, 1, "host"))
    plan["flash_blocks"] = {"512x128": [256, 512]}
    plancache.store(plan, str(tmp_path))
    cfg = Config(plan_cache_dir=str(tmp_path))
    assert plancache.bootstrap(cfg, _topo(), mode="tcp") == plan
    assert plancache.tuned_warm_start() == (1 << 25, 3.5, True)
    assert pk._plan(512, 128) == before
    assert before[:2] == (512, 512)
