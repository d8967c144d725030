"""Schema smoke for the pod-day readiness artifact.

The real podcheck number (allreduce efficiency >= 0.90 of ICI link
bandwidth, BASELINE.md) needs a multi-chip slice; this test validates
that ``benchmarks/podcheck.py --cpu-smoke`` produces the one-artifact
JSON the first hardware session will ship — so pod day starts with a
known-good entry point instead of improvisation (VERDICT r4 Next #7).
"""

import json
import os
import sys

from tests.utils.spawn import run_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_podcheck_smoke_artifact_schema(tmp_path):
    out = tmp_path / "podcheck.json"
    proc = run_world(
        [sys.executable, os.path.join(REPO, "benchmarks", "podcheck.py"),
         "--cpu-smoke", "--skip-autotune", "--out", str(out)],
        timeout=300, cwd=REPO)
    art = json.loads(out.read_text()) if out.exists() else {}
    # A section is a child of its own: what it wrote is in the artifact.
    assert proc.returncode == 0, "%s\n%s\n%s" % (
        proc.stdout[-2000:], proc.stderr[-2000:], "\n".join(
            "---- section %s (rc=%s) ----\n%s"
            % (sec["name"], sec.get("rc"), sec.get("tail"))
            for sec in art.get("sections", ()) if "rc" in sec))
    # BENCH_r*.json schema head.
    for key in ("metric", "value", "unit", "vs_baseline", "target",
                "pass", "sections", "smoke", "link_gbps"):
        assert key in art, "missing %r in artifact" % key
    assert art["metric"] == "allreduce_efficiency_vs_link"
    assert art["target"] == 0.90
    assert art["smoke"] is True
    by_name = {s["name"]: s for s in art["sections"]}
    assert set(by_name) == {"allreduce_bw", "scaling_efficiency",
                            "bench", "autotune_ab",
                            "hier_allgather_ab"}
    # The bandwidth section must have run and carried the summary line
    # the headline is computed from.
    bw = by_name["allreduce_bw"]
    assert bw["ok"], bw
    assert any(r.get("metric") == "allreduce_bus_bandwidth_peak"
               for r in bw["records"]), bw["records"]
    assert by_name["scaling_efficiency"]["ok"]
    # bench needs the real chip; smoke marks it skipped, not failed.
    assert by_name["bench"]["skipped"] is True
    assert by_name["autotune_ab"]["skipped"] is True  # --skip-autotune
    # The non-allreduce pod A/B (hier legs off vs on) must have run
    # both arms and produced the eager allgather records.
    hier = by_name["hier_allgather_ab"]
    assert hier["ok"], hier
    assert len(hier["arms"]) == 2
    for arm in hier["arms"]:
        assert any(r.get("metric") == "allgather_bus_bandwidth_peak"
                   for r in arm["records"]), arm
