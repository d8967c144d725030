"""bench.py is the driver-recorded artifact (BENCH_r*.json): a broken
harness loses the round's tracked metric, so smoke it on the CPU
fallback with a tiny config and validate the JSON contract."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_emits_one_valid_json_line():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["HVD_TPU_BENCH_BATCH"] = "2"
    env["HVD_TPU_BENCH_IMAGE"] = "32"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, "exactly one JSON line expected: %r" % lines
    d = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "mfu",
                "step_ms", "batch", "peak_tflops", "device_kind"):
        assert key in d, key
    assert d["metric"] == "resnet50_images_per_sec_per_chip"
    assert d["value"] > 0 and d["step_ms"] > 0
    # No peak on record for a CPU: utilization is null, never a probe.
    assert d["mfu"] is None and d["peak_tflops"] is None
    assert d["transformer_tok_s"] > 0 and d["transformer_mfu"] is None
    # r9: per-lever attribution block — flash block plan + bwd variant
    # + hier-op mode, so a BENCH delta is attributable to one lever.
    lev = d["levers"]
    flash = lev["flash"]
    assert flash["source"] in ("env", "autotuned", "default",
                               "fallback_xla")
    assert flash["bwd"] in ("onepass", "two_kernel", "chunked", "xla")
    assert "block_q" in flash and "block_k" in flash
    assert lev["hier"]["mode"] in ("auto", "on", "off")
    assert set(lev["hier"]["ops"]) == {
        "allreduce", "allgather", "alltoall", "reducescatter",
        "broadcast"}
    # Collective-plan plane attribution (the persistent plan cache):
    # present even when the plane is off — the bench must always say
    # whether a warm start was in play.
    plan = lev["plan"]
    assert "enabled" in plan and "schema" in plan
    assert set(plan["apply"]) == {"cache", "kv", "tuned", "default"}
    assert "hits" in plan and "misses" in plan
    # r16 serving-plane attribution: the continuous-batching knobs +
    # autoscale policy + plan-cache warm-start a deployment would run
    # with (additive key; headline comes from serving_bw.py).
    serving = lev["serving"]
    assert serving["max_batch"] >= 1
    assert serving["max_wait_micros"] >= 0
    assert set(serving["autoscale"]) == {
        "up_qdepth", "down_qdepth", "interval_s", "cooldown_s"}
    assert serving["autoscale"]["up_qdepth"] > \
        serving["autoscale"]["down_qdepth"]
    assert set(serving["plan_warm_start"]) == {
        "enabled", "source", "hits"}
    # ISSUE 18 self-healing data-plane attribution: the deadline /
    # retry / degradation knobs plus the live failure evidence.
    res = lev["resilience"]
    for key in ("deadline_secs", "leg_max_retries", "demote_threshold",
                "reprobe_secs", "degrade_enabled", "wire_integrity",
                "demoted_routes", "leg_retries_total",
                "deadline_expired_total", "failures_by_reason"):
        assert key in res, key
    assert res["demoted_routes"] == []  # a clean bench run stays hier
    # ISSUE 19 steady-state fast-path attribution: frozen/thaw counters
    # + per-plane freezer state (additive key; present even when no
    # engine ran, degraded to counters-only).
    fp = lev["fastpath"]
    for key in ("frozen_cycles_total", "thaws_total", "thaws_by_reason",
                "planes"):
        assert key in fp, key


def test_allreduce_bw_amortization_math():
    # The small-message batching: a fake 2 us/op timer must be batched
    # up until the differential window clears the timer resolution,
    # and the recovered per-op time must stay exact.
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from allreduce_bw import bus_bytes, measure_per_op

    per_op_true = 2e-6

    def fake_timed(total_ops):
        return 1e-4 + per_op_true * total_ops  # fixed dispatch + ops

    per_op, opw, resolvable = measure_per_op(fake_timed, 10)
    assert resolvable
    assert opw > 10, "small ops were not amortized"
    assert abs(per_op - per_op_true) / per_op_true < 0.01
    # a big op needs no batching
    per_op2, opw2, r2 = measure_per_op(lambda k: 1e-3 * k, 10)
    assert r2 and opw2 == 10 and abs(per_op2 - 1e-3) < 1e-5
    # NCCL bus-bytes conventions
    assert bus_bytes("allreduce", 4, 100) == 2 * 3 / 4 * 100
    assert bus_bytes("allgather", 4, 100) == 3 * 100
    assert bus_bytes("reducescatter", 4, 100) == 3 / 4 * 100
    assert bus_bytes("alltoall", 4, 100) == 3 / 4 * 100
    assert bus_bytes("broadcast", 4, 100) == 3 / 4 * 100


def test_allreduce_bw_fault_leg_self_attributes():
    # The resilience A/B leg: --fault arms HVD_TPU_FAULT pre-init (the
    # parse-time registration of the new mh.leg.* drop sites is part of
    # what this proves) and the run ends with a self-attributing
    # resilience_levers JSON line.  The in-process CPU world has no
    # cross-host leg, so the armed fault must parse cleanly and the
    # run stay healthy — the evidence block shows zero demotions.
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HVD_TPU_FAULT", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "allreduce_bw.py"),
         "--eager", "--cpu-devices", "2", "--sizes-mb", "0.25",
         "--iters", "2", "--warmup", "1",
         "--fault", "mh.leg.drop:drop@times=1"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")]
    lev = [r for r in recs if r.get("metric") == "resilience_levers"]
    assert len(lev) == 1, recs
    assert lev[0]["fault"] == "mh.leg.drop:drop@times=1"
    res = lev[0]["levers"]["resilience"]
    for key in ("deadline_secs", "deadline_per_gib", "leg_max_retries",
                "leg_retry_backoff", "demote_threshold", "reprobe_secs",
                "degrade_enabled", "wire_integrity", "demoted_routes",
                "leg_retries_total", "deadline_expired_total",
                "failures_by_reason"):
        assert key in res, key
    assert res["demoted_routes"] == []
    # the bandwidth records themselves still printed (the A/B numbers)
    assert [r for r in recs
            if r.get("metric") == "allreduce_bus_bandwidth"], recs


def test_allreduce_bw_fast_path_leg_self_attributes():
    # The fast-path A/B leg: --fast-path on exports HOROVOD_FAST_PATH
    # pre-init, the warm streak trips on the in-process engine, and the
    # run ends with a self-attributing fastpath_levers JSON line whose
    # frozen-cycle count (negotiations skipped) is the A/B evidence.
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_FAST_PATH_WARM_CYCLES"] = "3"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "allreduce_bw.py"),
         "--eager", "--cpu-devices", "2", "--sizes-mb", "0.25",
         "--iters", "4", "--warmup", "2", "--fast-path", "on"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")]
    bw = [r for r in recs
          if r.get("metric") == "allreduce_bus_bandwidth"]
    assert bw, recs
    # per-size live-metrics reporting rode along
    for key in ("negotiation_cycles", "negotiation_cycles_skipped",
                "cycle_time_us"):
        assert key in bw[0], key
    lev = [r for r in recs if r.get("metric") == "fastpath_levers"]
    assert len(lev) == 1, recs
    fp = lev[0]["levers"]["fastpath"]
    assert fp["frozen_cycles_total"] > 0, fp  # negotiations skipped
    assert fp["planes"]["eager"]["enabled"] is True
    # the off leg must really negotiate every cycle
    env["HOROVOD_FAST_PATH"] = "1"  # ambient on; the flag must win
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "allreduce_bw.py"),
         "--eager", "--cpu-devices", "2", "--sizes-mb", "0.25",
         "--iters", "2", "--warmup", "1", "--fast-path", "off"],
        capture_output=True, text=True, timeout=60, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")]
    lev = [r for r in recs if r.get("metric") == "fastpath_levers"]
    assert len(lev) == 1, recs
    assert lev[0]["levers"]["fastpath"]["frozen_cycles_total"] == 0
    assert lev[0]["levers"]["fastpath"]["planes"]["eager"]["enabled"] \
        is False


def test_flash_roofline_smoke_schema():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "flash_roofline.py"),
         "--cpu-smoke"],
        capture_output=True, text=True, timeout=90, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.strip().startswith("{")]
    by_metric = {}
    for r in recs:
        by_metric.setdefault(r["metric"], []).append(r)
    assert by_metric["flash_block_sweep"], recs
    variants = {r["variant"] for r in by_metric["flash_bwd_variant"]
                if "error" not in r}
    assert variants == {"pallas", "pallas_onepass", "chunked"}
    summary = by_metric["flash_roofline"][0]
    for key in ("matmul_roofline_tflops", "best_block_q",
                "best_block_k", "best_bwd_variant",
                "best_fwd_frac_of_roofline"):
        assert key in summary, key
