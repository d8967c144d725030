"""The trace reduction against traces with known answers.

``fixtures/trace_by_hand.json`` is small enough to work out on paper; the
answers below were (times in microseconds, window 1,000-11,000):

    device ops          interval        own time
    fusion.0            0-1,200         200 inside the window
    fusion.1            1,500-2,000     500
    fusion.1            2,005-2,500     495   (a 5 us gap before it)
    while.1             3,000-7,000     400 = 4,000 - 3,600 nested
      fusion.2          3,100-4,100     1,000
      jvp__.1 (Mosaic)  4,200-5,200     1,000
      all-reduce-start.1 5,300-5,400    100
      fusion.3          5,400-6,400     1,000
      all-reduce-done.1 6,400-6,900     500
    all-reduce.2        8,000-9,000     1,000
    fusion.4            10,500-12,000   500 inside the window

    busy  = 200 + 995 + 4,000 + 1,000 + 500 = 6,695;  idle = 3,305
    collectives' own time = 100 + 500 + 1,000 = 1,600;  compute = 5,095
    a collective under way: 5,300-6,900 (its own pair and the async line),
      8,000-9,000, and 9,500-9,800 (async line only; the copy there is no
      collective) = 2,900
    idle gaps: 1,200-1,500 (dispatch), 2,000-2,005 (short),
      2,500-3,000 (dispatch covers 300 of 500), 7,000-8,000 (the engine's
      span covers 800 and is shorter than block), 9,000-10,500 (block)

``fixtures/trace_v5e_*.json.gz``, where present, were recorded on the chip;
for them the reduction is checked against a brute-force count on a grid.
"""

import bisect
import glob
import gzip
import json
import os

import pytest

from yardstick import trace as tr

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
US = 1e-6


@pytest.fixture(scope="module")
def by_hand():
    with open(os.path.join(FIXTURES, "trace_by_hand.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(by_hand):
    return tr.reduce_chip(by_hand, 0, tr.span_window(by_hand,
                                                     "yardstick.traced"))


def test_window_and_chips(by_hand):
    assert tr.chips(by_hand) == [0]
    assert tr.span_window(by_hand, "yardstick.traced") == (1_000_000,
                                                           11_000_000)
    with pytest.raises(tr.TraceError):
        tr.span_window(by_hand, "yardstick.nothing")
    with pytest.raises(tr.TraceError):
        tr.device_ops(by_hand, 3)


@pytest.mark.parametrize("key,want_us", [
    ("window_s", 10_000), ("busy_s", 6_695), ("collective_s", 1_600),
    ("compute_s", 5_095), ("collective_flight_s", 2_900)])
def test_totals(reduced, key, want_us):
    assert reduced[key] == pytest.approx(want_us * US, rel=1e-12)


@pytest.mark.parametrize("name,want_us,count", [
    ("fusion.0", 200, 1), ("fusion.1", 995, 2), ("while.1", 400, 1),
    ("jvp__.1", 1_000, 1), ("all-reduce-done.1", 500, 1),
    ("fusion.4", 500, 1)])
def test_own_time_by_op(reduced, name, want_us, count):
    assert reduced["ops"][name]["seconds"] == pytest.approx(want_us * US)
    assert reduced["ops"][name]["count"] == count


def test_parse_op():
    """An HLO instruction as the v5e trace prints it, and a bare name."""
    info = tr.parse_op(
        '%closed_call.33 = (bf16[64,512,128]{2,1,0:T(8,128)(2,1)S(1)}, '
        'f32[64,512,1]{2,1,0:T(8,128)S(1)}) custom-call(bf16[64,512,128]'
        '{2,1,0:T(8,128)(2,1)S(1)} %pad.71), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert (info["name"], info["opcode"], info["target"]) == (
        "closed_call.33", "custom-call", "tpu_custom_call")
    assert info["label"].startswith("closed_call.33 tpu_custom_call (bf16[64")
    info = tr.parse_op('%all-reduce-start.2 = f32[1024]{0:T(1024)} '
                       'all-reduce-start(f32[1024]{0:T(1024)} %p), '
                       'replica_groups={{0,1,2,3}}')
    assert (info["name"], info["opcode"]) == ("all-reduce-start.2",
                                              "all-reduce-start")
    assert tr.is_collective(info["name"])
    assert not tr.is_collective("fusion.7")
    assert tr.parse_op("fusion.7") == {"name": "fusion.7", "opcode": "",
                                       "target": "", "label": "fusion.7"}


def test_idle_gaps_and_their_names(by_hand, reduced):
    gaps = reduced["idle_gaps"]
    assert gaps == [(1_200_000, 1_500_000), (2_000_000, 2_005_000),
                    (2_500_000, 3_000_000), (7_000_000, 8_000_000),
                    (9_000_000, 10_500_000)]
    assert tr.total(gaps) * 1e-9 == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    spans = [s for s in tr.host_spans(by_hand) if s[0] != "yardstick.traced"]
    named = tr.name_gaps(gaps, spans)
    assert named == pytest.approx({
        "yardstick.dispatch": 800 * US, tr.SHORT_GAPS: 5 * US,
        "hvd.mh.allreduce[3]": 1_000 * US, "yardstick.block": 1_500 * US})
    assert tr.name_gaps([(0, 50_000)], []) == pytest.approx(
        {tr.NO_SPAN: 50 * US})
    assert tr.top(named, 2) == [["yardstick.block", 1_500 * US],
                                ["hvd.mh.allreduce[3]", 1_000 * US]]


def test_readers_on_the_hand_trace(reduced):
    """The per-layer readers that take their number from the trace."""
    from yardstick.readers import (bus_gbps, idle_share, kernel_roofline,
                                   trace_ms_per_step)
    ev = {"traced": {"reduction": reduced, "steps": 2}, "chips": 4,
          "grad_bytes": 100_000_000, "device": {"kind": "TPU v5 lite"},
          "kernels": [{"kernel": "flash", "calls_per_step": 1, "per_call": {
              "fwd": {"flops": 197e12 * 100e-6, "bytes": 1.0},
              "bwd": {"flops": 1.0, "bytes": 819e9 * 150e-6}}}]}
    ms = trace_ms_per_step.read
    assert ms(ev, {"select": "compute"}) == pytest.approx(5.095 / 2)
    assert ms(ev, {"select": "collective"}) == pytest.approx(0.8)
    flash = {"select": "match", "pattern": "^tpu_custom_call$"}
    assert ms(ev, flash) == pytest.approx(0.5)
    assert ms(ev, {"select": "match", "pattern": "no such op"}) == 0.0
    assert ms({"traced": None}, flash) is None
    assert idle_share.read(ev, {}) == pytest.approx(33.05)
    # 2(n-1)/n x 100 MB = 150 MB; a collective under way 1.45 ms a step
    assert bus_gbps.read(ev, {}) == pytest.approx(150e6 / 1.45e-3 / 1e9)
    assert bus_gbps.read(dict(ev, chips=1), {}) is None
    # floor 100 us by operations + 150 us by bytes, against 500 us a step
    params = {"kernel": "flash", "pattern": flash["pattern"]}
    assert kernel_roofline.read(ev, params) == pytest.approx(50.0)
    assert kernel_roofline.floor_seconds(ev, params)[1] == "bytes+flops"
    assert kernel_roofline.read(dict(ev, kernels=[]), params) is None


def brute_force_busy(ops, lo, hi, step):
    """Busy time by sampling: the share of grid points at which some
    operation that started earlier has not ended yet."""
    ops = sorted((s, e) for _, s, e, _ in ops)
    starts = [s for s, _ in ops]
    latest_end, top = [], float("-inf")
    for _, e in ops:
        top = max(top, e)
        latest_end.append(top)
    hits = n = 0
    t = lo + step / 2
    while t < hi:
        i = bisect.bisect_right(starts, t) - 1
        hits += i >= 0 and latest_end[i] > t
        n += 1
        t += step
    return (hi - lo) * hits / n


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    FIXTURES, "trace_v5e_*.json.gz"))) or [None])
def test_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded chip trace in fixtures/")
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    window = tr.span_window(data, "yardstick.traced")
    red = tr.reduce_chip(data, tr.chips(data)[0], window)
    ops = [o for o in tr.device_ops(data, tr.chips(data)[0])
           if o[2] > window[0] and o[1] < window[1]]
    assert len(ops) > 100
    # Own times add up to the busy time where nothing overlaps but nests.
    assert sum(o["seconds"] for o in red["ops"].values()) == pytest.approx(
        red["busy_s"], rel=1e-9)
    assert red["compute_s"] + red["collective_s"] == pytest.approx(
        red["busy_s"], rel=1e-9)
    step = (window[1] - window[0]) / 20_000
    assert brute_force_busy(ops, window[0], window[1], step) * 1e-9 == \
        pytest.approx(red["busy_s"], rel=0.02)
    assert 0 < red["busy_s"] <= red["window_s"]
    # Mosaic kernels are leaves: their own time is their duration.
    mosaic = sum(e - s for n, s, e, info in ops
                 if info["target"] == "tpu_custom_call")
    assert mosaic > 0
    assert sum(o["seconds"] for o in red["ops"].values()
               if o["info"]["target"] == "tpu_custom_call") == \
        pytest.approx(mosaic * 1e-9, rel=1e-9)
