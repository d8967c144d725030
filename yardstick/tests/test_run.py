"""``run.py`` measures on a TPU or not at all, and the arithmetic from a
run's evidence to its result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from yardstick import manifest as mf
from yardstick import report

ARGS = ["--seed", "0", "--seconds", "1", "--trace", "0"]


def run_py(cwd, workload, env):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "yardstick", "run.py"),
         "--workload", workload] + ARGS,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("workload", ["resnet50.dp1", "resnet50.dp4-jit"])
def test_refuses_the_cpu(workload):
    """No result line and a non-zero exit under JAX_PLATFORMS=cpu, before
    anything of size is built."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = run_py(mf.ROOT, workload, env)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "no result" in proc.stderr and "cpu" in proc.stderr


EAGER_ENTRIES = [
    {"name": "world_form_s", "unit": "s", "better": "lower",
     "source": "host_clock", "layer": "launcher", "moves": "setup_s",
     "workloads": ["resnet50.np4-eager"]},
    {"name": "exchange_ms_per_step", "unit": "ms", "better": "lower",
     "source": "host_clock", "layer": "engine", "moves": "step_ms",
     "workloads": ["resnet50.np4-eager"]},
    {"name": "engine_cycles_per_step", "unit": "1", "better": "lower",
     "source": "program_counter", "layer": "engine", "moves": "step_ms",
     "workloads": ["resnet50.np4-eager"]},
]


def test_the_eager_world_needs_one_entry_and_refuses_the_cpu(tmp_path):
    """``resnet50.np4-eager`` is not a cell yet (PERF.md section 7), but
    its job, worker, workload file and readers are here: with its entry in
    BENCHMARK.json, in a copy, the manifest is sound and the four ranks,
    started through the real launcher, refuse the CPU."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(mf.ROOT, "yardstick"),
                    os.path.join(root, "yardstick"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["chips"] == 4:          # one four-chip cell in four
            w.update(name="resnet50.np4-eager", traffic="np4-eager")
    for m in bench["per_layer"]:
        if "resnet50.dp4-jit" in m.get("workloads", ()):
            m["workloads"] = ["resnet50.np4-eager"]
    bench["per_layer"] += EAGER_ENTRIES
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=mf.ROOT)
    env.pop("XLA_FLAGS", None)
    check = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from yardstick import manifest\n"
         "m = manifest.load()\n"
         "assert m.dir.startswith(%r), m.dir\n"
         "print(m.cell('resnet50.np4-eager')['job'],"
         " [x['name'] for x in m.metrics('per_layer', 'resnet50.np4-eager')"
         " if 'workloads' in x])" % (root, root)],
        cwd=root, env=env, capture_output=True, text=True, timeout=60)
    assert check.returncode == 0, check.stderr
    assert check.stdout.split()[0] == "eager_world"
    for name in ("world_form_s", "exchange_ms_per_step",
                 "engine_cycles_per_step", "allreduce_bus_gbps"):
        assert name in check.stdout
    proc = run_py(root, "resnet50.np4-eager", env)
    assert proc.returncode != 0, proc.stdout
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert "refused" in proc.stderr and "cpu" in proc.stderr


def test_refuses_an_unknown_cell_and_a_bare_directory(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = run_py(mf.ROOT, "no.such-cell", env)
    assert proc.returncode != 0 and not proc.stdout.strip()
    # Alone with BENCHMARK.json: the system under test is not there.
    root = str(tmp_path)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(mf.ROOT, "yardstick"),
                    os.path.join(root, "yardstick"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    env.pop("PYTHONPATH", None)
    proc = run_py(root, "resnet50.dp1", env)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


EVIDENCE = {
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    "chips": 1, "samples_per_step": 128, "flops_per_sample": 12.267e9,
    "grad_bytes": 100, "kernels": [],
    "t_start": 100.0, "t_init": [110.0], "t_window": 140.5,
    "first_step_s": 7.5, "cache": {"requests": 8, "hits": 6, "compiles": 9},
    "compiles_in_window": 0,
    "chunks": [[1.0, 20], [0.9, 20], [1.4, 20]], "window_s": 3.3,
    "steps": 60, "failed_steps": 0,
    "losses": {"reference": 6.9, "first": 6.9, "warm": 6.0, "last": 5.0},
    "checks": {"a": True, "b": True},
    "peak_bytes": [5 << 30], "counters": {"engine_cycles_total": 120.0},
    "traced": None,
}


def test_end_to_end_arithmetic():
    m = mf.load()
    line = report.result_line(m, "resnet50.dp1", EVIDENCE, traced=False)
    assert line["correct"] is True
    assert (line["attempted"], line["failed"]) == (60, 0)
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 5 << 30}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got == pytest.approx({
        "step_ms": 50.0,                           # median of 50, 45, 70
        "samples_per_s_per_chip": 128 * 60 / 3.3,  # the whole window
        "peak_hbm_gib": 5.0, "setup_s": 40.5})
    assert {k: v["unit"] for k, v in line["metrics"].items()} == {
        "step_ms": "ms", "samples_per_s_per_chip": "1/s",
        "peak_hbm_gib": "GiB", "setup_s": "s"}
    json.dumps(line)
    bad = dict(EVIDENCE, checks={"a": True, "b": False})
    assert report.result_line(m, "resnet50.dp1", bad, False)["correct"] \
        is False


@pytest.mark.parametrize("first,later,fell", [
    (7.007, [4.688, 0.204], True),
    # The 4/4 classification batch that stays near ln 2 (my chip run, PR 22).
    (0.7052, [0.6856, 0.6506], True),
    # Above step 0 straight after the warm-up, below it later.
    (0.6950, [0.6990, 0.6930, 0.6700], True),
    (0.6950, [0.6950, 0.6950], False),           # an optimizer that does nothing
    (0.6950, [0.7400, 1.9000], False),           # one that diverges
])
def test_loss_fell(first, later, fell):
    from yardstick import measure
    assert measure.loss_fell(first, later) is fell


def test_per_layer_without_a_trace_leaves_trace_metrics_out():
    m = mf.load()
    line = report.result_line(m, "resnet50.dp4-jit",
                              dict(EVIDENCE, chips=4), traced=True)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got == pytest.approx({
        "compile_cache_hit_share": 75.0, "first_step_s": 7.5,
        "compiles_in_window": 0,
        "model_flops_util": 100 * (128 * 60 / 3.3 / 4) * 12.267e9 / 197e12})
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_readers_of_the_eager_world():
    from yardstick.readers import counter_per_step, exchange, world_form
    ev = dict(EVIDENCE, t_launch=101.0, t_init=[110.0, 112.0],
              traced={"exchange_s": [0.010, 0.030, 0.014], "steps": 3})
    assert world_form.read(ev, {}) == 11.0
    assert world_form.read(EVIDENCE, {}) is None
    assert exchange.read(ev, {}) == pytest.approx(14.0)
    assert exchange.read(EVIDENCE, {}) is None
    params = {"counter": "engine_cycles_total"}
    assert counter_per_step.read(ev, params) == 2.0
    assert counter_per_step.read(ev, {"counter": "no_such"}) is None
