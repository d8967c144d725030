"""What PR 27 added to the benchmark: the hybrid decoder's operation
counts against a hand count, its entries in the manifest against the
source's published config, the ``scope_roofline`` reader on the recorded
scoped trace, and the new cell end to end at its tiny size on the CPU."""

import gzip
import json
import os
import subprocess
import sys

import pytest

from yardstick import flops, flops_hybrid as fh, manifest as mf, peaks
from yardstick import trace as tr
from yardstick.readers import (kernel_roofline, scope_ms_per_step,
                               scope_roofline)

CELL = "solar-open2-250b.dp1-pt8k"
FIXTURES = os.path.join(mf.ROOT, mf.PACKAGE, "fixtures")

# The catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Solar-Open2-250B), as published.
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "num_experts_per_tok")


def shapes():
    from yardstick.builders import solar_open2
    return solar_open2._shapes(mf.load().cell(CELL))


# -- operations and bytes -----------------------------------------------------

def test_the_models_operations_against_a_hand_count():
    """Per token, forward multiply-adds (ISSUE 27's table): a linear mixer
    holds 4 x 4096 x 1024 + 2 x (4096 x 128 + 128 x 1024) + 4096 x 8 +
    3 x 4 x 1024 = 18,132,992, a softmax mixer 3 x 4096 x 1024 + 2 x 4096
    x 128 = 13,631,488, every layer a router 4096 x 320, a shared expert
    3 x 4096 x 1280 and 8 x 8 / 320 of a routed expert, the head 4096 x
    24576."""
    parts = fh.forward_macs_per_token(**shapes())
    core = 8 * (4 * 64 * 64 * 128 + 4 * 64 * 128 * 128 + 128 ** 3) / 64
    assert fh.delta_rule_macs(64, 128) == 8388608
    assert parts == {
        "head": 4096 * 24576,
        "projections": 3 * 18132992 + 13631488,
        "delta_rule": 3 * core,
        "softmax": 8193 * 8 * 128,
        "router": 4 * 4096 * 320,
        "shared_expert": 4 * 3 * 4096 * 1280,
        "routed_experts": 4 * 0.2 * 3 * 4096 * 1280}
    total = sum(parts.values())
    assert total == pytest.approx(261.3e6, rel=2e-3)
    assert fh.train_flops_per_sequence(**shapes()) == 6 * 8192 * total
    # about 1.57 GFLOP a token and 12.8 TFLOP a sequence
    assert 6 * total == pytest.approx(1.568e9, rel=2e-3)
    share = {k: v / total for k, v in parts.items()}
    assert share["head"] == pytest.approx(0.385, abs=0.005)
    assert share["shared_expert"] == pytest.approx(0.241, abs=0.005)
    assert share["routed_experts"] == pytest.approx(0.048, abs=0.005)
    assert share["delta_rule"] < 0.015 and share["softmax"] < 0.04


def test_the_kernels_costs_against_a_hand_count():
    v5e = peaks.peak_of("TPU v5 lite")
    kda = fh.delta_rule_cost(2, 8192, 8, 128, 64)
    chunks = 2 * 128 * 8
    assert kda["fwd"]["flops"] == 2 * chunks * 8388608
    assert kda["bwd"]["flops"] == 2 * kda["fwd"]["flops"]
    tensor = 2 * 8192 * 8 * 128 * 4
    assert kda["fwd"]["bytes"] == 5 * tensor + 2 * 8192 * 8 * 4
    assert kda["bwd"]["bytes"] == 9 * tensor + 2 * 2 * 8192 * 8 * 4
    # 34 GFLOP (0.17 ms) against 0.34 GB (0.41 ms): the bytes set the floor
    assert flops.roofline_seconds(kda["fwd"]["flops"], kda["fwd"]["bytes"],
                                  v5e) == (kda["fwd"]["bytes"] / 819e9,
                                           "bytes")
    pairs = fh.expected_pairs(16384, 8, 8, 320)
    assert pairs == pytest.approx(3276.8)
    experts = fh.expert_products_cost(pairs, 8, 4096, 1280)
    assert experts["fwd"]["flops"] == pytest.approx(
        2 * 3 * 3276.8 * 4096 * 1280)
    assert experts["fwd"]["bytes"] == pytest.approx(
        3 * 8 * 4096 * 1280 * 4 + 2 * 3276.8 * 4096 * 2)
    assert experts["bwd"]["flops"] == 2 * experts["fwd"]["flops"]
    # 410 rows an expert: reading the float32 weights sets the floor
    assert flops.roofline_seconds(experts["fwd"]["flops"],
                                  experts["fwd"]["bytes"], v5e)[1] == "bytes"


def test_the_builders_kernels_entries():
    from yardstick.builders import solar_open2
    entries = {k["kernel"]: k for k in solar_open2._kernels(
        mf.load().cell(CELL), 2)}
    assert entries["kda_core"]["calls_per_step"] == 3
    assert entries["experts"]["calls_per_step"] == 4
    assert entries["kda_core"]["per_call"] == fh.delta_rule_cost(
        2, 8192, 8, 128, 64)
    # the one softmax layer's kernels: 8 query heads of 128 over 8192,
    # causal; both passes 4.9 ms at the v5e's peak, the operations' floor
    assert entries["flash"]["calls_per_step"] == 1
    assert entries["flash"]["per_call"] == flops.flash_attention_cost(
        2, 8, 8192, 128, causal=True)
    v5e = peaks.peak_of("TPU v5 lite")
    floor = [flops.roofline_seconds(c["flops"], c["bytes"], v5e)
             for c in entries["flash"]["per_call"].values()]
    assert {bound for _, bound in floor} == {"flops"}
    assert sum(s for s, _ in floor) == pytest.approx(4.88e-3, rel=0.01)


# -- what the builder sets before the first step ---------------------------------

def test_the_cells_load_profile():
    """The held experts' loads as the cell's file gives them: the same
    pairs as an even routing gives (``0.2 T``, what ``experts_roofline``
    counts), 2.7 times the mean load at most, three and two blocks of 512
    rows for the two fullest experts and none within 40 tokens of a
    block's end."""
    from yardstick.builders import solar_open2
    cell = mf.load().cell(CELL)
    goal = solar_open2.load_targets(cell, 2 * 8192)
    assert goal.shape == (320,) and goal.sum() == pytest.approx(8 * 16384)
    held = goal[:8]
    assert held.sum() == pytest.approx(0.2 * 16384)
    assert held.max() / goal.mean() == pytest.approx(2.7)
    rows = cell["spec"]["expert_block_rows"]
    assert [int(-(-n // rows)) for n in held] == [3, 2, 1, 1, 1, 1, 1, 1]
    assert min(min(n % rows, rows - n % rows) for n in held) > 40
    assert set(goal[8:].round(6)) == {409.6}


@pytest.mark.parametrize("tokens, experts, top_k, common", [
    (16384, 320, 8, 1.0), (4096, 320, 8, 2.0), (256, 8, 2, 1.0)])
def test_fit_router_bias_reaches_the_loads_it_is_given(tokens, experts,
                                                       top_k, common):
    """Scores with an offset of their own for every expert, as random
    router columns against hidden states with a common part give them:
    the fitted buffer brings every expert within a fiftieth of the mean
    load of its target, in a few dozen rounds."""
    import jax
    import numpy as np

    from yardstick.builders import solar_open2
    rng = np.random.default_rng(tokens)
    logits = 0.7 * rng.normal(size=(tokens, experts)) \
        + common * rng.normal(size=(1, experts))
    scores = jax.nn.sigmoid(np.asarray(logits, np.float32))
    shares = np.ones(experts)
    shares[:2] = [1.5, 0.5]
    goal = shares * tokens * top_k / experts
    bias, rounds = jax.jit(lambda s: solar_open2.fit_router_bias(
        s, goal, top_k))(scores)
    _, ids = jax.lax.top_k(scores + bias, top_k)
    loads = np.bincount(np.asarray(ids).ravel(), minlength=experts)
    assert np.abs(loads - goal).max() \
        <= solar_open2.ROUTER_FIT_WITHIN * goal.mean()
    assert int(rounds) < 100
    # with no bias the offsets leave some expert at twice its load
    _, ids = jax.lax.top_k(scores, top_k)
    plain = np.bincount(np.asarray(ids).ravel(), minlength=experts)
    assert (plain / goal).max() > 2


# -- the manifest ---------------------------------------------------------------

def test_the_configuration_is_the_source_cut_as_it_says():
    manifest = mf.load()
    assert manifest.problems() == []
    entry = manifest._entry("configs", "solar-open2-250b")
    held = mf.read_json(os.path.join(mf.ROOT, entry["file"]))
    assert entry["source"] == held["source"]
    assert sorted(entry["reduced"]) == sorted(held["reduced"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert held[key] != value, key
            assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
        else:
            assert held[key] == value, key
    lin = held["linear_attn_config"]
    assert (lin["head_dim"], lin["short_conv_kernel_size"]) == (128, 4)
    # the floors of a model_config PR: a whole period, 8 experts, an
    # eighth of the vocabulary; the published counts stand beside the cut
    assert held["num_hidden_layers"] == held["gqa_interval"] + 1
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key, value in held["published"].items():
        top, _, inner = key.partition(".")
        assert (PUBLISHED[top][inner] if inner else PUBLISHED[top]) == value
    assert set(held["assumed"]) >= {"gate_rank", "decay", "gqa_gate",
                                    "router", "init"}


def test_the_cell_and_its_metrics():
    manifest = mf.load()
    cell = manifest._entry("workloads", CELL)
    assert (cell["chips"], cell["config"]) == (1, "solar-open2-250b")
    assert "410" in cell["why"] and len(cell["why"]) <= 200
    spec = manifest.cell(CELL)["spec"]
    assert (spec["job"], spec["seq_len"]) == ("jit_step", 8192)
    reported = [m["name"] for m in manifest.metrics("per_layer", CELL)]
    for name in ("forward_ms_per_step", "backward_ms_per_step",
                 "optimizer_ms_per_step", "exchange_pack_ms_per_step",
                 "unscoped_ms_per_step", "attention_ms_per_step",
                 "head_ms_per_step", "flash_bwd_ms_per_step",
                 "linear_attention_ms_per_step", "kda_core_ms_per_step",
                 "moe_ms_per_step", "router_ms_per_step",
                 "experts_ms_per_step", "kda_core_roofline",
                 "experts_roofline", "model_flops_util",
                 "device_idle_share", "flash_roofline"):
        assert name in reported, name
    for name in reported[-7:]:
        entry = manifest._entry("per_layer", name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "step_ms"
    # the old cells report nothing new
    assert not set(reported[-7:]) & {
        m["name"] for m in manifest.metrics("per_layer",
                                            "bert-large.dp1-mlm512")}


# -- the reader -------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    def load(name):
        with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
            return json.load(f)
    trace = load("trace_v5e_bert_mlm512_scoped.json.gz")
    red = tr.reduce_chip(trace, 0, tr.span_window(trace, "yardstick.traced"))
    cost = flops.flash_attention_cost(4, 16, 512, 64, causal=False)
    return {"device": {"kind": "TPU v5 lite"},
            "kernels": [{"kernel": "flash", "calls_per_step": 24,
                         "per_call": cost}],
            "traced": {"reduction": red, "steps": 2, "op_names": load(
                "op_names_v5e_bert_mlm512_scoped.json.gz")}}


def test_scope_roofline_on_the_recorded_trace(recorded):
    """The recorded BERT step's flash kernels selected by their scopes:
    the scopes also hold the little XLA does round each kernel, so the
    share is a little under ``kernel_roofline``'s, which selects the
    custom calls alone."""
    scopes = ["hvd.flash_fwd", "hvd.flash_dq", "hvd.flash_dkv"]
    share = scope_roofline.read(recorded, {"kernel": "flash",
                                           "scopes": scopes})
    floor, bound = kernel_roofline.floor_seconds(recorded,
                                                 {"kernel": "flash"})
    took = scope_ms_per_step.read(recorded, {"scopes": scopes})
    assert bound == "flops"
    assert share == pytest.approx(100.0 * floor / (took / 1e3), rel=1e-12)
    kernels_alone = kernel_roofline.read(
        recorded, {"kernel": "flash", "pattern": "^tpu_custom_call$"})
    assert kernels_alone == pytest.approx(23.1027, rel=1e-4)    # PERF.md
    assert 0.95 * kernels_alone < share < kernels_alone


def test_scope_roofline_reads_nothing_where_there_is_nothing(recorded):
    params = {"kernel": "kda_core", "scopes": ["hvd.kda_core"]}
    # the cell has no such kernel; the program has no such scope
    assert scope_roofline.read(recorded, params) is None
    assert scope_roofline.read(recorded, {"kernel": "flash",
                                          "scopes": ["hvd.kda_core"]}) is None
    # no trace; a program from before the scopes
    assert scope_roofline.read(dict(recorded, traced=None),
                               {"kernel": "flash",
                                "scopes": ["hvd.flash_fwd"]}) is None
    bare = dict(recorded, traced=dict(recorded["traced"], op_names={
        "names": {n: "" for n in recorded["traced"]["op_names"]["names"]}}))
    assert scope_roofline.read(bare, {"kernel": "flash",
                                      "scopes": ["hvd.flash_fwd"]}) is None


# -- end to end, tiny, on the CPU -----------------------------------------------

def test_the_new_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "yardstick", "rehearse.py"),
         "cpu", CELL], cwd=mf.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads([ln for ln in proc.stdout.splitlines()
                       if ln.startswith("{")][-1])
    assert line["correct"] and line["rehearsal"] and line["attempted"] > 0
