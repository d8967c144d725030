"""What PR 31 added to the benchmark: the banded flash call's and the
Laguna-XS.2 share's operation counts against a hand count, the
configuration against the source's published config, the cell and its
metrics in the manifest, the builder's ``kernels`` entries under the readers
the benchmark has, and the new cell end to end at its tiny size on the
CPU."""

import json
import os
import subprocess
import sys

import pytest

from yardstick import flops, flops_hybrid as fh, flops_window as fw
from yardstick import manifest as mf, peaks
from yardstick.readers import kernel_roofline, scope_roofline

CELL = "laguna-xs2.dp1-pt8k"
NEW_METRICS = ("window_attention_ms_per_step", "window_flash_ms_per_step",
               "window_flash_roofline")
PERIOD = ["full_attention"] + ["sliding_attention"] * 3

# The catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Laguna-XS.2), as published.
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "num_experts_per_tok", "sliding_window")


def cell():
    return mf.load().cell(CELL)


def shapes():
    from yardstick.builders import laguna
    return laguna._shapes(cell())


# -- operations and bytes -----------------------------------------------------

def test_a_banded_call_against_a_hand_count():
    """A query meets ``min(i + 1, window)`` keys: 512 x 513 / 2 for the
    first 512 queries of 8192, 512 each for the other 7680."""
    assert fw.band_pairs(8192, 512) == 131328 + 7680 * 512 == 4063488
    assert fw.band_pairs(8, 3) == 1 + 2 + 3 * 6
    assert fw.band_pairs(100, 512) == 100 * 101 // 2      # the triangle
    cost = fw.window_flash_cost(2, 64, 8192, 128, 512)
    product = 2.0 * 2 * 64 * 4063488 * 128
    tensor = 2 * 64 * 8192 * 128 * 2
    assert cost == {"fwd": {"flops": 2 * product, "bytes": 4 * tensor},
                    "bwd": {"flops": 5 * product, "bytes": 8 * tensor}}
    # an eighth of the full triangle's work, a little under: 496 keys a
    # query against 4096.5
    full = flops.flash_attention_cost(2, 64, 8192, 128, causal=True)
    assert cost["fwd"]["flops"] / full["fwd"]["flops"] \
        == pytest.approx(4063488 / (8192 * 8192 / 2))
    v5e = peaks.peak_of("TPU v5 lite")
    assert {flops.roofline_seconds(c["flops"], c["bytes"], v5e)[1]
            for c in cost.values()} == {"flops"}


def test_the_models_operations_against_a_hand_count():
    """Per token, forward multiply-adds (ISSUE 31's table): a full mixer
    3 x 2048 x 6144 + 2 x 2048 x 1024 = 41,943,040, a sliding one 3 x 2048
    x 8192 + the same = 54,525,952; a full layer's softmax 8193 x 48 x 128,
    a sliding layer's 2 x (4,063,488 / 8192) x 64 x 128; the dense layer
    3 x 2048 x 8192; each sparse layer a router 2048 x 256, a shared expert
    3 x 2048 x 512 and 8 x 32 / 256 = one routed expert; the head 2048 x
    12,544."""
    parts = fw.forward_macs_per_token(**shapes())
    assert parts == {
        "head": 2048 * 12544,
        "projections": 2 * 41943040 + 3 * 54525952,
        "softmax": 2 * 8193 * 48 * 128,
        "window_softmax": 3 * 2 * (4063488 / 8192.0) * 64 * 128,
        "dense": 3 * 2048 * 8192,
        "router": 4 * 2048 * 256,
        "shared_expert": 4 * 3 * 2048 * 512,
        "routed_experts": 4 * 3 * 2048 * 512}
    total = sum(parts.values())
    share = {part: round(100 * macs / total) for part, macs in parts.items()}
    assert share == {"head": 5, "projections": 52, "softmax": 21,
                     "window_softmax": 5, "dense": 11, "router": 0,
                     "shared_expert": 3, "routed_experts": 3}
    assert fw.train_flops_per_sequence(**shapes()) \
        == pytest.approx(2 * 3 * 8192 * total)
    # 46.8 T operations a step of two sequences: 237 ms at the v5e's peak
    assert 2 * 6 * 8192 * total == pytest.approx(46.78e12, rel=1e-3)


def test_the_builders_kernels_entries():
    from yardstick.builders import laguna
    entries = laguna._kernels(cell(), 2)
    assert [(k["kernel"], k["calls_per_step"]) for k in entries] == [
        ("experts", 4), ("flash", 2), ("flash", 3), ("flash_window", 3)]
    experts, full, banded, again = entries
    assert experts["per_call"] == fh.expert_products_cost(
        16384.0, 32, 2048, 512)
    assert full["per_call"] == flops.flash_attention_cost(
        2, 48, 8192, 128, causal=True)
    assert banded["per_call"] == again["per_call"] \
        == fw.window_flash_cost(2, 64, 8192, 128, 512)
    # what the readers make of them: ``flash_roofline``'s floor counts the
    # full calls and the banded ones, ``window_flash_roofline``'s the
    # banded ones alone (operations set both: 58.6 and 14.2 ms at the peak)
    ev = {"kernels": entries, "device": {"kind": "TPU v5 lite"}}
    both, bound = kernel_roofline.floor_seconds(ev, {"kernel": "flash"})
    alone, _ = kernel_roofline.floor_seconds(ev, {"kernel": "flash_window"})
    assert bound == "flops" and alone == pytest.approx(14.2e-3, rel=0.01)
    assert both - alone == pytest.approx(58.6e-3, rel=0.01)
    # no trace, nothing to read
    assert scope_roofline.read(dict(ev, traced=None), {
        "kernel": "flash_window", "scopes": ["hvd.flash_window_fwd"]}) is None


# -- what the builder sets before the first step ---------------------------------

def test_the_cells_load_profile():
    """The 32 held experts' loads as the cell's file gives them: the pairs
    an even routing gives (``T``), 2.7 times the mean load at most, the
    eight values four times over, 12 blocks of 512 rows for each eight and
    none within 40 tokens of a block's end."""
    from yardstick.builders import laguna
    spec = cell()["spec"]
    goal = laguna.load_targets(cell(), 2 * 8192)
    assert goal.shape == (256,) and goal.sum() == pytest.approx(8 * 16384)
    held = goal[:32]
    assert held.sum() == pytest.approx(16384)
    assert held.max() / goal.mean() == pytest.approx(2.7)
    assert held.min() / goal.mean() == pytest.approx(0.1)
    assert (held.reshape(4, 8) == held[:8]).all()
    rows = spec["expert_block_rows"]
    assert [int(-(-n // rows)) for n in held[:8]] == [3, 2, 2, 1, 1, 1, 1, 1]
    assert min(min(n % rows, rows - n % rows) for n in held) > 40
    assert set(goal[32:].round(6)) == {512.0}
    assert sum(spec["expert_load_profile"]) == pytest.approx(8)


# -- the manifest ------------------------------------------------------------------

def test_the_configuration_is_the_source_cut_as_it_says():
    manifest = mf.load()
    assert manifest.problems() == []
    entry = manifest._entry("configs", "laguna-xs2")
    held = mf.read_json(os.path.join(mf.ROOT, entry["file"]))
    assert entry["source"] == held["source"]
    assert sorted(entry["reduced"]) == sorted(held["reduced"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert held[key] != value, key
            assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
        else:
            assert held[key] == value, key
    # the per-layer lists are the published lists' first five entries
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert held[key] == PUBLISHED[key][:5], key
    # the floors of a model_config PR: the leading dense layer, a whole
    # period of four layers after it, 8 experts or more, an eighth of the
    # vocabulary; the published counts stand beside the cut
    assert held["num_hidden_layers"] == 1 + len(PERIOD)
    assert held["num_experts"] >= 8
    assert held["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert held["published"][key] == PUBLISHED[key]
    assert set(held["assumed"]) >= {"gating", "router", "rotary", "qk_norm",
                                    "init", "optimizer", "head"}
    assert "scalar gate a head" in held["assumed"]["gating"]
    # the cut's arithmetic, as the file's ``cut_is`` has it
    full = 3 * 2048 * 6144 + 2 * 2048 * 1024
    sliding = 3 * 2048 * 8192 + 2 * 2048 * 1024
    sparse = 3 * 2048 * 512 + 2048 * 256 + 256 + 32 * 3 * 2048 * 512
    norms = 2 * 2048
    assert held["parameters"] == (
        full + 3 * 2048 * 8192 + norms + 3 * (sliding + sparse + norms)
        + full + sparse + norms + 2 * 12544 * 2048 + 2048) == 766532608
    assert held["parameters"] * 16 / 2 ** 30 == pytest.approx(11.42, abs=0.01)


def test_the_cell_and_its_metrics():
    manifest = mf.load()
    entry = manifest._entry("workloads", CELL)
    assert (entry["chips"], entry["config"]) == (1, "laguna-xs2")
    assert "eighth" in entry["why"] and len(entry["why"]) <= 200
    spec = manifest.cell(CELL)["spec"]
    assert (spec["job"], spec["seq_len"], spec["batch_per_chip"],
            spec["chunk_steps"], spec["head_block"]) \
        == ("jit_step", 8192, 2, 1, 4096)
    reported = [m["name"] for m in manifest.metrics("per_layer", CELL)]
    for name in ("forward_ms_per_step", "backward_ms_per_step",
                 "optimizer_ms_per_step", "exchange_pack_ms_per_step",
                 "unscoped_ms_per_step", "attention_ms_per_step",
                 "head_ms_per_step", "flash_bwd_ms_per_step",
                 "moe_ms_per_step", "router_ms_per_step",
                 "router_rows_ms_per_step", "experts_ms_per_step",
                 "experts_roofline", "flash_roofline", "flash_ms_per_step",
                 "model_flops_util", "device_idle_share") + NEW_METRICS:
        assert name in reported, name
    for name in ("kda_core_roofline", "linear_attention_ms_per_step"):
        assert name not in reported, name
    assert tuple(reported[-3:]) == NEW_METRICS
    for name in NEW_METRICS:
        metric = manifest._entry("per_layer", name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "step_ms"
        reader, params = manifest.layer_metric(name)
        assert all(s.startswith("hvd.") and "window" in s
                   for s in params["scopes"])
    assert manifest.layer_metric("window_flash_roofline") == (
        "scope_roofline", {"kernel": "flash_window", "scopes": [
            "hvd.flash_window_fwd", "hvd.flash_window_dq",
            "hvd.flash_window_dkv"]})
    # the old cells report nothing new
    for old in ("bert-large.dp1-mlm512", "solar-open2-250b.dp1-pt8k"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in manifest.metrics("per_layer", old)}
    # six cells allow one four-chip cell
    assert [w["chips"] for w in manifest.bench["workloads"]].count(4) == 1


def test_the_scopes_the_metrics_read_are_the_programs():
    from horovod_tpu.common import scopes
    manifest = mf.load()
    assert manifest.layer_metric("window_attention_ms_per_step")[1] \
        == {"scopes": [scopes.WINDOW_ATTENTION]}
    assert manifest.layer_metric("window_flash_ms_per_step")[1] == {
        "scopes": [scopes.FLASH_WINDOW_FWD, scopes.FLASH_WINDOW_DQ,
                   scopes.FLASH_WINDOW_DKV]}


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
