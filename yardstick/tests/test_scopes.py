"""From an operation to its scope: the HLO text handling, the phase rule,
the choice of the step's module among those a trace holds, the HLO a trace
file carries, and the reader, on a
hand-made HLO text whose instruction names are those of
``fixtures/trace_by_hand.json``.  With the own times of that trace (see
``test_trace.py``; microseconds, 2 steps):

    operation     own    op_name                          phase      blocks
    fusion.0      200    (printed with no metadata)       unscoped
    fusion.1      995    transpose(jvp(hvd.model))/hvd.head  backward  head
    while.1       400    jvp(hvd.model)/while             forward
    fusion.2    1,000    jvp(hvd.model)/.../hvd.attention forward    attention
    jvp__.1     1,000    transpose(...)/hvd.attention/hvd.flash_dq
                                                          backward   attention,
                                                                     flash_dq
    fusion.3    1,000    hvd.optimizer/hvd.exchange       exchange
    fusion.4      500    hvd.optimizer                    optimizer
    all-reduce-start.1, -done.1, all-reduce.2: collectives, never counted

    forward 1,400, backward 1,995, optimizer 500, exchange 1,000,
    unscoped 200: 5,095 = compute; attention 2,000, head 995, flash_bwd 1,000
"""

import glob
import gzip
import json
import os

import pytest

from yardstick import manifest as mf
from yardstick import scopes
from yardstick import trace as tr
from yardstick.readers import scope_ms_per_step, trace_ms_per_step

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")

MODEL = "jit(step)/shard_map/jvp(hvd.model)"
BACK = "jit(step)/shard_map/transpose(jvp(hvd.model))"
FLASH_DQ = (BACK + "/while/body/closed_call/hvd.attention/hvd.flash_dq"
            "/hvd_flash_dq/pallas_call")

# As ``HloModule.to_string()`` prints a module: a fused computation, the
# body and condition of a while loop, the entry; names with and without
# ``%``; ROOT; escaped quotes in a parameter's name.
HLO = r'''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %multiply.7 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="''' + MODEL + r'''/while/body/mul" source_file="m.py" source_line=3}
  ROOT %add.9 = f32[8]{0} add(%multiply.7, %param_0.1), metadata={op_name="''' + MODEL + r'''/while/body/hvd.attention/add" source_file="m.py" source_line=4}
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.5 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.2 = f32[8]{0} fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="''' + MODEL + r'''/while/body/hvd.attention/add" source_file="m.py" source_line=4}
  %jvp__.1 = (bf16[64,512,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[64,512,1]{2,1,0}) custom-call(%fusion.2), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[8]{0}}, metadata={op_name="''' + FLASH_DQ + r'''" source_file="k.py" source_line=424}
  %all-reduce-start.1 = f32[8]{0} all-reduce-start(%fusion.2), replica_groups={{0,1,2,3}}, to_apply=%add.clone, metadata={op_name="jit(step)/shard_map/hvd.optimizer/hvd.exchange/psum"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/shard_map/hvd.optimizer/hvd.exchange/concatenate"}
  %all-reduce-done.1 = f32[8]{0} all-reduce-done(%all-reduce-start.1), metadata={op_name="jit(step)/shard_map/hvd.optimizer/hvd.exchange/psum"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%get-tuple-element.5, %fusion.3)
}

ENTRY %main.42 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params[\'w\']"}
  fusion.0 = f32[8]{0:T(256)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.0
  %fusion.1 = f32[8]{0} fusion(fusion.0), kind=kInput, calls=%fused_computation.1, metadata={op_name="''' + BACK + r'''/hvd.head/mul;''' + BACK + r'''/hvd.head/broadcast_in_dim"}
  %while.1 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="''' + MODEL + r'''/while"}
  %all-reduce.2 = f32[8]{0} all-reduce(%fusion.1), to_apply=%add.clone, metadata={op_name="jit(step)/shard_map/psum"}
  ROOT %fusion.4 = f32[8]{0} fusion(%all-reduce.2), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/shard_map/hvd.optimizer/sub"}
}
'''


def test_op_names_over_every_computation():
    names = scopes.op_names(HLO)
    assert names["fusion.2"] == MODEL + "/while/body/hvd.attention/add"
    assert names["add.9"] == names["fusion.2"]      # a fusion has its root's
    assert names["multiply.7"] == MODEL + "/while/body/mul"
    assert names["jvp__.1"] == FLASH_DQ
    assert names["while.1"] == MODEL + "/while"
    assert names["fusion.4"] == "jit(step)/shard_map/hvd.optimizer/sub"
    assert names["fusion.0"] == ""                  # printed with no metadata
    assert names["get-tuple-element.5"] == "" and names["tuple.3"] == ""
    assert names["Arg_0.1"] == r"params[\'w\']"
    assert names["fusion.1"].count(";") == 1
    # computations' own headers and the module's line are no instructions
    assert not {"HloModule", "ENTRY", "main.42", "body.1",
                "fused_computation.2"} & set(names)
    assert len(names) == 17


@pytest.mark.parametrize("op_name,phase,found", [
    (MODEL + "/dot_general", "forward", {"hvd.model"}),
    (BACK + "/dot_general", "backward", {"hvd.model"}),
    (MODEL + "/while/body/hvd.attention/add", "forward",
     {"hvd.model", "hvd.attention"}),
    (MODEL + "/hvd.head/reduce_sum", "forward", {"hvd.model", "hvd.head"}),
    (FLASH_DQ, "backward", {"hvd.model", "hvd.attention", "hvd.flash_dq"}),
    # outside any step builder, as a kernel differentiated alone
    ("jit(f)/transpose(jvp(hvd.flash_dkv))/hvd_flash_dkv/pallas_call",
     "unscoped", {"hvd.flash_dkv"}),
    # the inner scope wins
    ("jit(step)/hvd.optimizer/hvd.exchange/concatenate", "exchange",
     {"hvd.optimizer", "hvd.exchange"}),
    ("jit(step)/hvd.optimizer/sub", "optimizer", {"hvd.optimizer"}),
    # not differentiated, so no jvp round the scope
    ("jit(step)/hvd.model/convert_element_type", "forward", {"hvd.model"}),
    # names of merged operations: the later phase of the two
    (MODEL + "/mul;" + BACK + "/mul", "backward", {"hvd.model"}),
    (BACK + "/mul;jit(step)/hvd.optimizer/add", "optimizer",
     {"hvd.model", "hvd.optimizer"}),
    ("jit(step)/shard_map/psum", "unscoped", set()),
    (r"params[\'w\']", "unscoped", set()),
    ("", "unscoped", set()),
])
def test_classify(op_name, phase, found):
    assert scopes.classify(op_name) == (phase, found)


def test_merge_takes_the_step_and_fills_from_the_others():
    step = scopes.op_names(HLO)
    other = {"fusion.1": "jit(other)/mul", "fusion.77": "jit(other)/add",
             "copy.1": ""}
    third = {"fusion.77": "jit(third)/add", "fusion.1": "jit(third)/mul"}
    seen = ["fusion.0", "fusion.1", "fusion.2", "while.1", "fusion.77",
            "copy.1", "nowhere.3"]
    for modules in ([other, step, third], [step, third, other]):
        got = scopes.merge(seen, modules)
        assert got["names"]["fusion.1"] == step["fusion.1"]
        assert got["names"]["fusion.77"] in ("jit(other)/add",
                                             "jit(third)/add")
        assert got["names"]["copy.1"] == ""
        assert "nowhere.3" not in got["names"]
        assert (got["filled"], got["ambiguous"], got["missing"]) == (
            2, 1, ["nowhere.3"])
        assert got["main_share"] == pytest.approx(4 / 7)
    assert scopes.merge([], []) == {"names": {}, "main_share": 0.0,
                                    "filled": 0, "ambiguous": 0,
                                    "missing": []}
    assert scopes.merge(["a.1"], [])["missing"] == ["a.1"]


@pytest.fixture(scope="module")
def evidence():
    with open(os.path.join(FIXTURES, "trace_by_hand.json")) as f:
        trace = json.load(f)
    red = tr.reduce_chip(trace, 0, tr.span_window(trace, "yardstick.traced"))
    return {"traced": {"reduction": red, "steps": 2,
                       "op_names": scopes.merge(red["ops"],
                                                [scopes.op_names(HLO)])}}


METRICS = {
    "forward_ms_per_step": 0.7, "backward_ms_per_step": 0.9975,
    "optimizer_ms_per_step": 0.25, "exchange_pack_ms_per_step": 0.5,
    "unscoped_ms_per_step": 0.1, "attention_ms_per_step": 1.0,
    "head_ms_per_step": 0.4975, "flash_bwd_ms_per_step": 0.5}
PHASES = [
    "forward_ms_per_step", "backward_ms_per_step", "optimizer_ms_per_step",
    "exchange_pack_ms_per_step", "unscoped_ms_per_step"]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_metric_on_the_hand_trace(evidence, metric):
    """Read as ``report.py`` reads it: the reader and the params that the
    metric's own ``layer_metrics`` file names."""
    manifest = mf.load()
    reader, params = manifest.layer_metric(metric)
    assert reader == "scope_ms_per_step"
    assert manifest.module("readers", reader).read(evidence, params) == \
        pytest.approx(METRICS[metric], rel=1e-12)


def test_the_phases_add_up_to_compute(evidence):
    manifest = mf.load()
    assert evidence["traced"]["op_names"]["missing"] == []
    total = sum(scope_ms_per_step.read(evidence,
                                       manifest.layer_metric(m)[1])
                for m in PHASES)
    assert total == pytest.approx(
        trace_ms_per_step.read(evidence, {"select": "compute"}), rel=1e-12)
    assert total == pytest.approx(5.095 / 2)


def test_nothing_to_read_and_nothing_selected(evidence):
    read = scope_ms_per_step.read
    phase = {"phase": "forward"}
    assert read({"traced": None}, phase) is None
    assert read({}, phase) is None
    traced = evidence["traced"]
    # a rehearsal: chunks, no reduction
    assert read({"traced": {"steps": 2}}, phase) is None
    # a reduction whose trace file is not to be found (no ``t_window``)
    assert read({"traced": {"reduction": traced["reduction"], "steps": 2}},
                phase) is None
    # a program that names nothing: every op_name there, none with a scope
    bare = {name: "jit(step)/mul" for name in traced["op_names"]["names"]}
    assert read({"traced": dict(traced, op_names={"names": bare})},
                phase) is None
    # a scope no operation carries reads 0, not nothing
    assert read(evidence, {"scopes": ["hvd.flash_bwd_onepass"]}) == 0.0
    # an operation no module holds is unscoped
    fewer = {k: v for k, v in traced["op_names"]["names"].items()
             if k != "fusion.4"}
    ev = {"traced": dict(traced, op_names={"names": fewer})}
    assert read(ev, {"phase": "optimizer"}) == 0.0
    assert read(ev, {"phase": "unscoped"}) == pytest.approx(0.35)
    for bad in ({"phase": "sideways"}, {}, {"phase": "forward",
                                            "scopes": ["hvd.head"]}):
        with pytest.raises(ValueError):
            read(evidence, bad)


CELLS = {"resnet50.dp1", "resnet50.dp4-jit", "bert-large.dp1-mlm512",
         "bert-large.dp1-ft384"}
BERT = {"bert-large.dp1-mlm512", "bert-large.dp1-ft384"}


@pytest.mark.parametrize("metric,layer,cells", [
    ("forward_ms_per_step", "step", CELLS),
    ("backward_ms_per_step", "step", CELLS),
    ("optimizer_ms_per_step", "step", CELLS),
    ("exchange_pack_ms_per_step", "collectives", CELLS),
    ("unscoped_ms_per_step", "step", CELLS),
    ("attention_ms_per_step", "step", BERT),
    ("head_ms_per_step", "step", BERT),
    ("flash_bwd_ms_per_step", "kernels", {"bert-large.dp1-mlm512"})])
def test_the_manifest_holds_the_eight(metric, layer, cells):
    manifest = mf.load()
    entry = manifest._entry("per_layer", metric)
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"], entry["layer"]) == (
        "ms", "lower", "device_trace", "step_ms", layer)
    reporting = {w["name"] for w in manifest.bench["workloads"]
                 if metric in [m["name"] for m in
                               manifest.metrics("per_layer", w["name"])]}
    assert reporting == cells


def test_the_scope_names_are_the_programs():
    """The benchmark spells the phase scopes itself (it must read a
    program that has none); they are the program's."""
    from horovod_tpu.common import scopes as program
    assert (scopes.MODEL, scopes.OPTIMIZER, scopes.EXCHANGE) == (
        program.MODEL, program.OPTIMIZER, program.EXCHANGE)
    selected = set()
    for path in glob.glob(os.path.join(mf.ROOT, mf.PACKAGE, "layer_metrics",
                                       "*.json")):
        with open(path) as f:
            selected |= set(json.load(f).get("params", {}).get("scopes", ()))
    vocabulary = {v for k, v in vars(program).items() if k.isupper()}
    assert selected and selected <= vocabulary
    assert all(scopes.SCOPE.fullmatch(v) for v in vocabulary)


def test_the_recorded_traces_reduce_as_before():
    """The numbers the accepted metrics read from the recorded v5e trace,
    as they were before this reader existed."""
    with gzip.open(os.path.join(
            FIXTURES, "trace_v5e_bert_mlm512.json.gz"), "rt") as f:
        data = json.load(f)
    red = tr.reduce_chip(data, 0, tr.span_window(data, "yardstick.traced"))
    assert (red["window_s"], red["busy_s"], red["compute_s"],
            red["collective_s"], len(red["ops"])) == pytest.approx(
        (0.13058823, 0.13055937375, 0.13055937375, 0.0, 660), rel=1e-12)
    assert trace_ms_per_step.selected_seconds(
        red, {"select": "match", "pattern": "^tpu_custom_call$"}) == \
        pytest.approx(0.0158540275, rel=1e-9)


# -- the HLO a trace file carries ------------------------------------------

def varint(n):
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    return bytes(out + bytes([n]))


def field(number, payload):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(payload, int):
        return varint(number << 3) + varint(payload)
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def hlo_proto(names, module_name):
    """A serialized ``HloProto`` of one computation with the fields round
    the two that are read: opcode, shape, ids past one byte, a fixed64 and
    a fixed32 of the wire format; no ``metadata`` where the name is ""."""
    instructions = b"".join(
        field(2, field(1, name.encode()) + field(2, b"fusion")
              + field(3, field(2, 11) + field(3, b"\x08\x80\x01"))
              + (field(7, field(1, b"add") + field(2, op_name.encode())
                       + field(4, 300)) if op_name else b"")
              + field(35, 70000 + i)
              + varint(50 << 3 | 1) + bytes(8) + varint(51 << 3 | 5)
              + bytes(4))
        for i, (name, op_name) in enumerate(names.items()))
    module = (field(1, module_name) + field(2, b"main.42")
              + field(3, field(1, b"main.42") + instructions + field(5, 42))
              + field(5, 101))
    return field(1, module) + field(3, b"\x0a\x00")


def xspace(protos):
    """A serialized ``XSpace``: a device plane whose event metadata also
    holds bytes, then the metadata plane with one program for each of
    ``protos``."""
    def program(i, proto):
        metadata = (field(1, i) + field(2, b"jit_step(%d)" % i)
                    + field(5, field(1, 1) + field(6, proto)))
        return field(4, field(1, i) + field(2, metadata))
    device = (field(1, 2) + field(2, b"/device:TPU:0")
              + program(7, b"not an HloProto")
              + field(6, field(1, 9) + varint(2 << 3 | 1) + bytes(8)))
    metadata = (field(1, 3) + field(2, scopes.METADATA_PLANE.encode())
                + b"".join(program(101 + i, p) for i, p in enumerate(protos))
                + field(5, field(1, 1) + field(2, field(1, 1)
                                               + field(2, b"Hlo Proto"))))
    return field(1, device) + field(1, metadata) + field(4, b"vm")


OTHER = {"fusion.1": "jit(other)/mul", "copy.1": ""}


def test_the_hlo_of_a_trace_file():
    step = scopes.op_names(HLO)
    blob = hlo_proto(step, b"jit_step")
    assert scopes.proto_op_names(blob) == step
    assert scopes.proto_op_names(memoryview(blob)) == step
    protos = list(scopes.trace_hlo_protos(
        memoryview(xspace([hlo_proto(OTHER, b"jit_other"), blob]))))
    assert [scopes.proto_op_names(p) for p in protos] == [OTHER, step]
    assert list(scopes.trace_hlo_protos(field(1, field(2, b"/host:CPU")))) \
        == []
    with pytest.raises(ValueError):
        list(scopes.submessages(varint(1 << 3 | 3), 1))     # a group


def write_trace(root, cell, protos, mtime):
    path = os.path.join(str(root), cell, "trace", "plugins", "profile",
                        "2026_09_30", "vm.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with open(path, "wb") as f:
        f.write(xspace(protos))
    os.utime(path, (mtime, mtime))
    return path


def test_the_reader_finds_the_trace_this_run_wrote(evidence, tmp_path,
                                                   monkeypatch):
    """No ``op_names`` in the evidence: the reader takes them from the
    newest trace written since the window began, once, and leaves them
    beside the reduction."""
    monkeypatch.setattr(scope_ms_per_step, "TRACES", os.path.join(
        str(tmp_path), "*", "trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    step = hlo_proto(scopes.op_names(HLO), b"jit_step")
    write_trace(tmp_path, "an-earlier-cell", [hlo_proto(OTHER, b"jit_other")],
                mtime=1000.0)
    assert scope_ms_per_step.traced_since(1000.5) is None
    mine = write_trace(tmp_path, "this-cell",
                       [hlo_proto(OTHER, b"jit_other"), step], mtime=2000.0)
    assert scope_ms_per_step.traced_since(999.0) == mine
    assert scope_ms_per_step.traced_since(1500.0) == mine

    def fresh(t_window):
        traced = {k: v for k, v in evidence["traced"].items()
                  if k != "op_names"}
        return {"traced": traced, "t_window": t_window}
    manifest = mf.load()
    ev = fresh(1500.0)
    for metric, value in METRICS.items():
        assert scope_ms_per_step.read(
            ev, manifest.layer_metric(metric)[1]) == pytest.approx(value)
    found = ev["traced"]["op_names"]
    assert (found["modules"], found["main_share"], found["missing"]) == (
        2, 1.0, [])
    assert found["names"] == evidence["traced"]["op_names"]["names"]
    # a window that began after the last trace was written has none
    late = fresh(2500.0)
    assert scope_ms_per_step.read(late, {"phase": "forward"}) is None
    assert late["traced"]["op_names"] is None


# Two whole steps of the ``bert-large.dp1-mlm512`` traced run of PR 25 on
# the v5e, cut out of its tail, with the ``op_names`` read for it (from the
# text of the loaded executables, which gives what the trace file's own HLO
# gives).  The answers (ms a step) were worked out once by another route:
# the plain durations of the op line's events, the ``while``'s nested
# operations taken out of it, grouped by substring tests on the op_name.
RECORDED = {
    "forward_ms_per_step": 19.905170625,
    "backward_ms_per_step": 27.817906875,
    "optimizer_ms_per_step": 13.827124375,
    "exchange_pack_ms_per_step": 0.0,
    "unscoped_ms_per_step": 3.751251875,
    "attention_ms_per_step": 18.01793875,
    "head_ms_per_step": 3.507799375,
    "flash_bwd_ms_per_step": 4.4785925}


@pytest.fixture(scope="module")
def recorded():
    def load(name):
        with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
            return json.load(f)
    trace = load("trace_v5e_bert_mlm512_scoped.json.gz")
    red = tr.reduce_chip(trace, 0, tr.span_window(trace, "yardstick.traced"))
    return {"traced": {
        "reduction": red, "steps": 2,
        "op_names": load("op_names_v5e_bert_mlm512_scoped.json.gz")}}


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_each_metric_on_the_recorded_pair(recorded, metric):
    manifest = mf.load()
    reader, params = manifest.layer_metric(metric)
    assert manifest.module("readers", reader).read(recorded, params) == \
        pytest.approx(RECORDED[metric], rel=1e-9)


def test_the_recorded_pair_is_whole(recorded):
    traced = recorded["traced"]
    names = traced["op_names"]
    # every traced operation was found, all of them in the step's module
    # (the map also holds the events of no duration, which the reduction
    # drops)
    assert set(names["names"]) >= set(traced["reduction"]["ops"])
    assert (names["missing"], names["filled"], names["main_share"]) == (
        [], 0, 1.0)
    compute = trace_ms_per_step.read(recorded, {"select": "compute"})
    assert compute == pytest.approx(65.30145375, rel=1e-9)
    assert sum(RECORDED[m] for m in PHASES) == pytest.approx(compute,
                                                             rel=1e-9)
    # the backward kernels and the forward kernel are the flash kernels
    flash = trace_ms_per_step.read(
        recorded, {"select": "match", "pattern": "^tpu_custom_call$"})
    forward_kernel = 1e3 / 2 * traced["reduction"]["ops"][
        "hvd_flash_fwd.7"]["seconds"]
    assert flash - RECORDED["flash_bwd_ms_per_step"] == pytest.approx(
        forward_kernel, rel=1e-9)
    # the kernels carry the names the program gave them
    mosaic = {n for n, o in traced["reduction"]["ops"].items()
              if o["info"]["target"] == "tpu_custom_call"}
    assert mosaic == {"hvd_flash_fwd.7", "hvd_flash_dq.10",
                      "hvd_flash_dkv.10"}
