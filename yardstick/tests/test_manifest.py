"""The manifest: every name resolves, and a new cell, configuration, job
and per-layer metric need new files and one entry each, no edit."""

import json
import os
import shutil
import sys

import pytest

from yardstick import manifest as mf


def test_the_manifest_is_sound():
    m = mf.load()
    assert m.problems() == []
    cells = m.bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        cell = m.cell(w["name"])
        assert os.path.isfile(m.module_file("jobs", cell["job"]))
        assert os.path.isfile(m.module_file("builders", cell["builder"]))
        names = [x["name"] for x in m.metrics("end_to_end", w["name"])]
        assert "setup_s" in names and len(names) >= 2
        for metric in m.metrics("per_layer", w["name"]):
            reader, _ = m.layer_metric(metric["name"])
            assert callable(m.module("readers", reader).read)
    assert len(json.dumps(m.bench)) < 64 * 1024
    assert all(not c["reduced"] for c in m.bench["configs"])


@pytest.fixture
def copy(tmp_path):
    """A throw-away copy of the benchmark under another package name, so
    that importing from it cannot meet the real one."""
    root = str(tmp_path)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(mf.ROOT, mf.PACKAGE),
                    os.path.join(root, "yardcopy"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    sys.path.insert(0, root)
    yield root
    sys.path.remove(root)
    for name in [n for n in sys.modules if n.split(".")[0] == "yardcopy"]:
        del sys.modules[name]


def write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text)


def test_additions_need_only_files_and_an_entry(copy):
    before = {}
    for dirpath, _, files in os.walk(copy):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                before[path] = f.read()

    write(copy, "yardcopy/configs/toy.json", json.dumps(
        {"builder": "toy", "sample_unit": "rows", "width": 8}))
    write(copy, "yardcopy/builders/toy.py", "WIDTH = 8\n")
    write(copy, "yardcopy/jobs/toy_job.py",
          "def run(ctx):\n    return {'cell': ctx['cell']['name']}\n")
    write(copy, "yardcopy/workloads/toy.burst.json", json.dumps(
        {"job": "toy_job", "chunk_steps": 3, "burst": 7}))
    write(copy, "yardcopy/layer_metrics/toy_rows.json", json.dumps(
        {"reader": "toy_reader", "params": {"scale": 2}}))
    write(copy, "yardcopy/readers/toy_reader.py",
          "def read(ev, params):\n    return ev['rows'] * params['scale']\n")
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy", "source": "none",
                             "file": "yardstick/configs/toy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy.burst", "config": "toy",
                               "traffic": "burst", "chips": 1, "why": "t"})
    bench["per_layer"].append({
        "name": "toy_rows", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "toy", "moves": "step_ms",
        "workloads": ["toy.burst"]})
    # The copy's package has another name than BENCHMARK.json's paths say.
    text = json.dumps(bench).replace("yardstick/", "yardcopy/").replace(
        '"yardstick"', '"yardcopy"')
    write(copy, "BENCHMARK.json", text)

    m = mf.load(copy, "yardcopy")
    assert "toy.burst" in [w["name"] for w in m.bench["workloads"]]
    cell = m.cell("toy.burst")
    assert cell["spec"]["burst"] == 7 and cell["config"]["width"] == 8
    assert m.module("builders", cell["builder"]).WIDTH == 8
    assert m.module("jobs", cell["job"]).run({"cell": cell}) == {
        "cell": "toy.burst"}
    assert [x["name"] for x in m.metrics("per_layer", "toy.burst")
            if "workloads" in x] == ["toy_rows"]
    reader, params = m.layer_metric("toy_rows")
    assert m.module("readers", reader).read({"rows": 21}, params) == 42
    # ... and the other cells do not report the new metric.
    assert "toy_rows" not in [x["name"] for x in
                              m.metrics("per_layer", "resnet50.dp1")]
    # No file that was there has changed, BENCHMARK.json's entries aside.
    for path, data in before.items():
        if not path.endswith("BENCHMARK.json"):
            with open(path, "rb") as f:
                assert f.read() == data, path


@pytest.mark.parametrize("damage,says", [
    (lambda b: b["workloads"][0].update(name="resnet50.other"),
     "not named <config>.<traffic>"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0])),
     "used 2 times"),
    (lambda b: b["workloads"][0].update(chips=4), "ask for four chips"),
    (lambda b: b["workloads"][0].update(chips=2), "asks for 2 chips"),
    (lambda b: b["per_layer"][0].update(name="no_such_metric"),
     "cannot read"),
    (lambda b: b["per_layer"][0].update(moves="nothing"), "moves 'nothing'"),
    (lambda b: b["end_to_end"][0].update(bound=0.2), "bound of"),
    (lambda b: b["end_to_end"][0].update(source="program_span"),
     "has source"),
    (lambda b: b["configs"][0].update(name="bad name!"), "bad name"),
    (lambda b: b.update(extra=1), "keys are"),
    (lambda b: b["workloads"][0].update(why="x" * 201), "over 200"),
])
def test_a_damaged_manifest_is_refused(copy, damage, says):
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    damage(bench)
    write(copy, "BENCHMARK.json", json.dumps(bench).replace(
        "yardstick/", "yardcopy/").replace('"yardstick"', '"yardcopy"'))
    with pytest.raises(mf.ManifestError) as exc:
        mf.load(copy, "yardcopy")
    assert says in str(exc.value)


def test_a_missing_file_is_named(copy):
    os.remove(os.path.join(copy, "yardcopy", "readers", "idle_share.py"))
    os.remove(os.path.join(copy, "yardcopy", "workloads",
                           "resnet50.dp1.json"))
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        text = f.read()
    write(copy, "BENCHMARK.json", text.replace(
        "yardstick/", "yardcopy/").replace('"yardstick"', '"yardcopy"'))
    with pytest.raises(mf.ManifestError) as exc:
        mf.load(copy, "yardcopy")
    assert "readers/idle_share.py" in str(exc.value)
    assert "resnet50.dp1.json" in str(exc.value)
