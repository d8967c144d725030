"""``readers/program_spans.py`` on records made by hand, and the six
lifecycle metrics in the manifest."""

import pytest

from yardstick import manifest as mf
from yardstick.readers import program_spans

EV = {"t_start": 100.0, "t_window": 140.0}
#           id parent name                  start  end    attributes
RECORDS = [(1, None, "hvd.import",          100.5, 102.0, {}),
           (3, 2,    "hvd.init_devices",    102.1, 102.4, {}),
           (2, None, "hvd.init",            102.0, 103.0, {}),
           (4, None, "hvd.mesh",            103.0, 103.5, {}),
           (6, 5,    "hvd.optimizer_init",  104.2, 104.8, {}),
           (5, None, "hvd.build_state",     104.0, 106.0, {"leaves": 9}),
           # A jit traced while another is: nested stage records.
           (7, None, "hvd.compile_trace",   111.0, 112.0, {"fun_name": "f"}),
           (8, None, "hvd.compile_trace",   110.0, 114.0, {"fun_name": "g"}),
           (9, None, "hvd.compile_lower",   114.0, 115.0, {"fun_name": "g"}),
           (10, None, "hvd.compile_cache_read", 115.5, 116.5, {}),
           (11, None, "hvd.compile_backend", 115.0, 117.0, {"fun_name": "g"}),
           (12, None, "hvd.compile_backend", 120.0, 120.5, {"fun_name": "h"}),
           # Ends after the first measured step: not set-up's.
           (13, None, "hvd.compile_backend", 139.5, 140.5, {"fun_name": "w"}),
           (14, None, "hvd.shard_batch",    150.0, 151.0, {}),
           # Another process's past (a registry that outlived a world).
           (15, None, "hvd.init",           90.0, 99.0, {})]

WANT = {"init_s": 1.0,
        "state_build_s": 0.5 + 2.0,
        "trace_lower_s": 4.0 + 1.0,
        "executable_load_s": 2.0 + 0.5,
        "setup_programs": 2,
        "setup_in_program_s": 1.5 + 1.0 + 0.5 + 2.0 + 7.0 + 0.5}


@pytest.fixture
def records(monkeypatch):
    from horovod_tpu.common import metrics
    monkeypatch.setattr(metrics, "span_records", lambda: list(RECORDS),
                        raising=False)


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_metric_on_the_records(records, name):
    reader, params = mf.load().layer_metric(name)
    assert reader == "program_spans"
    value = program_spans.read(EV, params)
    assert value == pytest.approx(WANT[name])
    assert isinstance(value, int) == (name == "setup_programs")


def test_a_union_not_a_sum(records):
    nested = program_spans.read(EV, {"spans": ["hvd.compile_trace"]})
    assert nested == pytest.approx(4.0)             # not 1 + 4
    apart = program_spans.read(EV, {"spans": ["hvd.mesh", "hvd.init"]})
    assert apart == pytest.approx(1.5)
    touching = program_spans.read(
        EV, {"spans": ["hvd.compile_lower", "hvd.compile_backend"]})
    assert touching == pytest.approx(3.0 + 0.5)


def test_the_window_cuts_by_a_records_end(records):
    early = dict(EV, t_window=116.0)
    assert program_spans.read(early, {"spans": ["hvd.compile_backend"]}) == 0
    assert program_spans.read(
        early, {"spans": ["hvd.compile_backend"], "count": True}) == 0
    late = dict(EV, t_window=141.0)
    assert program_spans.read(
        late, {"spans": ["hvd.compile_backend"], "count": True}) == 3
    assert program_spans.in_setup(RECORDS, EV, ["hvd.shard_batch"]) == []
    # A set-up in which the program kept no span: nothing to read, not 0.
    assert program_spans.read({"t_start": 200.0, "t_window": 230.0}, {}) \
        is None


def test_nothing_to_read(records, monkeypatch):
    # A world's parent: the spans are its workers'.
    assert program_spans.read(dict(EV, t_launch=99.0), {}) is None
    # A program without host spans (the parent of the PR that added them).
    from horovod_tpu.common import metrics
    monkeypatch.delattr(metrics, "span_records")
    assert program_spans.read(EV, {}) is None
    assert program_spans.read(EV, {"spans": ["hvd.init"], "count": True}) \
        is None


def test_the_six_in_the_manifest():
    manifest = mf.load()
    cells = [w["name"] for w in manifest.bench["workloads"]][:7]
    entries = {m["name"]: m for m in manifest.bench["per_layer"]}
    for name in WANT:
        m = entries[name]
        assert (m["layer"], m["moves"], m["better"]) \
            == ("lifecycle", "setup_s", "lower")
        assert m["workloads"] == cells
        assert (m["unit"], m["source"]) == (
            ("1", "program_counter") if name == "setup_programs"
            else ("s", "program_span"))
    for cell in cells:
        reported = [m["name"] for m in manifest.metrics("per_layer", cell)]
        assert set(WANT) <= set(reported)
