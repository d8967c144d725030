"""What PR 33 added to the benchmark: the state-space scan's and the
Nemotron-3-Nano share's operation counts against a hand count, the
configuration against the source's published config, the parameter count
by hand against the builder's, the cell and its metrics in the manifest,
the builder's ``kernels`` entries under the readers the benchmark has, and
the new cell end to end at its tiny size on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from yardstick import flops, flops_ssm as fs
from yardstick import manifest as mf, peaks
from yardstick.readers import kernel_roofline, scope_roofline

CELL = "nemotron-3-nano-30b-a3b.dp1-pt8k"
NEW_METRICS = ("state_space_ms_per_step", "ssd_core_ms_per_step",
               "ssd_core_roofline")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# The catalog row's ``config`` (model-configs guide, architectures.jsonl,
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), as published.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
WIDTHS = ("hidden_size", "head_dim", "mamba_head_dim", "mamba_num_heads",
          "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
          "intermediate_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "num_experts_per_tok",
          "num_attention_heads", "num_key_value_heads")


def cell():
    return mf.load().cell(CELL)


def shapes():
    from yardstick.builders import nemotron_h
    return nemotron_h._shapes(cell())


# -- operations and bytes -----------------------------------------------------

def test_the_scan_against_a_hand_count_at_a_small_shape():
    """One chunk of 4 steps, 2 heads of 3 channels in 1 group, state 5:
    ``C B^T`` 4 x 4 x 5 = 80 once, then a head 4 x 4 x 3 = 48 for the
    masked product and 2 x 4 x 3 x 5 = 120 for the chunk's state and the
    carried state's part."""
    assert fs.ssd_macs(4, 2, 3, 1, 5) == 80 + 2 * (48 + 120) == 416
    cost = fs.ssd_cost(batch=3, seq=8, heads=2, head=3, groups=1, state=5,
                       chunk=4)
    product = 2.0 * 3 * 2 * 416
    x, bc, dt = 3 * 8 * 2 * 3 * 2, 2 * 3 * 8 * 5 * 2, 3 * 8 * 2 * 4
    assert cost == {"fwd": {"flops": product, "bytes": 2 * x + bc + dt},
                    "bwd": {"flops": 2 * product,
                            "bytes": 3 * x + 2 * (bc + dt)}}
    # the cell's: 1.70 M multiply-adds a token and layer; bytes set the
    # floor in both passes (0.42 and 0.67 ms a layer on the v5e)
    sh = shapes()
    per_token = fs.ssd_macs(128, 64, 64, 8, 128) / 128.0
    assert per_token == 128 * 128 * 8 + 64 * (128 * 64 + 2 * 64 * 128) \
        == 1703936
    cost = fs.ssd_cost(2, sh["seq"], 64, 64, 8, 128, 128)
    assert cost["fwd"]["flops"] == 2.0 * 16384 * per_token
    v5e = peaks.peak_of("TPU v5 lite")
    floors = {k: flops.roofline_seconds(c["flops"], c["bytes"], v5e)
              for k, c in cost.items()}
    assert {bound for _, bound in floors.values()} == {"bytes"}
    assert floors["fwd"][0] == pytest.approx(0.415e-3, rel=0.01)
    assert floors["bwd"][0] == pytest.approx(0.666e-3, rel=0.01)


def test_the_models_operations_against_a_hand_count():
    """Per token, forward multiply-adds (ISSUE 33's table): an M block
    2688 x 10,304 + 4096 x 2688 + 4 x 6144 of projections and convolution
    and 1,703,936 of scan; the * block 2 x 2688 x 4096 + 2 x 2688 x 256 and
    8193 x 32 x 128 of softmax; an E block a router 2688 x 128, a shared
    expert 2 x 2688 x 3712 and 6 x 8 / 128 of a routed expert of 2 x 2688
    x 1856; the head 2688 x 16,384."""
    parts = fs.forward_macs_per_token(**shapes())
    assert parts == {
        "head": 2688 * 16384,
        "ssm_projections": 4 * (2688 * 10304 + 4096 * 2688 + 4 * 6144),
        "scan": 4 * 1703936.0,
        "attention_projections": 2 * 2688 * 4096 + 2 * 2688 * 256,
        "softmax": 8193 * 32 * 128,
        "router": 4 * 2688 * 128,
        "shared_expert": 4 * 2 * 2688 * 3712,
        "routed_experts": 4 * 0.375 * 2 * 2688 * 1856}
    total = sum(parts.values())
    share = {part: round(100 * macs / total) for part, macs in parts.items()}
    assert share == {"head": 12, "ssm_projections": 43, "scan": 2,
                     "attention_projections": 7, "softmax": 9, "router": 0,
                     "shared_expert": 22, "routed_experts": 4}
    assert fs.train_flops_per_sequence(**shapes()) \
        == pytest.approx(2 * 3 * 8192 * total)
    # 35.3 T operations a step of two sequences: 179 ms at the v5e's peak
    assert 2 * 6 * 8192 * total == pytest.approx(35.3e12, rel=2e-3)


def test_the_builders_kernels_entries():
    from yardstick.builders import nemotron_h
    entries = nemotron_h._kernels(cell(), 2)
    assert [(k["kernel"], k["calls_per_step"]) for k in entries] == [
        ("ssd_core", 4), ("experts", 4), ("flash", 1)]
    scan, experts, flash = entries
    assert scan["per_call"] == fs.ssd_cost(2, 8192, 64, 64, 8, 128, 128)
    # two matrices an expert: 2 x 2 x pairs x 2688 x 1856 a forward pass
    # against the 8 held experts' float32 weights and the pairs' rows
    assert experts["per_call"] == fs.expert_products_cost(6144.0, 8, 2688,
                                                         1856)
    assert experts["per_call"]["fwd"] == {
        "flops": 2.0 * 2 * 6144 * 2688 * 1856,
        "bytes": 2 * 8 * 2688 * 1856 * 4 + 2 * 6144 * 2688 * 2}
    assert experts["per_call"]["bwd"]["flops"] \
        == 2 * experts["per_call"]["fwd"]["flops"]
    assert flash["per_call"] == flops.flash_attention_cost(
        2, 32, 8192, 128, causal=True)
    # what the readers make of them: the scan's floor is 4.3 ms a step
    ev = {"kernels": entries, "device": {"kind": "TPU v5 lite"}}
    floor, bound = kernel_roofline.floor_seconds(ev, {"kernel": "ssd_core"})
    assert bound == "bytes" and floor == pytest.approx(4.32e-3, rel=0.01)
    assert kernel_roofline.floor_seconds(ev, {"kernel": "kda_core"}) is None
    # no trace, nothing to read
    assert scope_roofline.read(dict(ev, traced=None), {
        "kernel": "ssd_core", "scopes": ["hvd.ssd_core"]}) is None


# -- what the builder sets before the first step ------------------------------

def test_the_cells_load_profile():
    """The 8 held experts' loads as the cell's file gives them: the 6,144
    pairs an even routing gives this chip, 2.75 times the mean load of 768
    at most, 17 blocks of 512 rows a layer and none within 64 tokens of a
    block's end."""
    from yardstick.builders import nemotron_h
    spec = cell()["spec"]
    goal = nemotron_h.load_targets(cell(), 2 * 8192)
    assert goal.shape == (128,) and goal.sum() == pytest.approx(6 * 16384)
    held = goal[:8]
    assert held.sum() == pytest.approx(6144) and goal.mean() == 768
    assert [round(n) for n in held] == [2112, 1229, 883, 691, 576, 384, 192,
                                        77]
    rows = spec["expert_block_rows"]
    blocks = [int(-(-n // rows)) for n in held]
    assert blocks == [5, 3, 2, 2, 2, 1, 1, 1] and sum(blocks) == 17
    assert min(min(n % rows, rows - n % rows) for n in held) >= 64
    assert set(goal[8:].round(6)) == {768.0}
    assert sum(spec["expert_load_profile"]) == pytest.approx(8)


# -- the manifest -------------------------------------------------------------

def test_the_configuration_is_the_source_cut_as_it_says():
    manifest = mf.load()
    assert manifest.problems() == []
    entry = manifest._entry("configs", "nemotron-3-nano-30b-a3b")
    held = mf.read_json(os.path.join(mf.ROOT, entry["file"]))
    assert entry["source"] == held["source"]
    assert entry["reduced"] == held["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert held[key] != value, key
            assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
            assert held["published"][key] == value, key
        else:
            assert held[key] == value, key
    # the pattern's first nine letters: 4 M, 4 E, 1 *, the published
    # 23 : 23 : 6 to within a block
    assert held["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert held["num_hidden_layers"] == 9
    assert [PATTERN.count(k) for k in "ME*"] == [23, 23, 6]
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert set(held["assumed"]) >= {
        "positions", "inner_width", "time_step_limit", "gated_norm",
        "rescale_prenorm_residual", "router", "sequences", "init",
        "optimizer", "head", "loss"}
    assert "float32" in held["state_dtype"]


def test_the_parameter_count_by_hand_against_the_builders():
    """The cut's arithmetic, as the file's ``cut_is`` has it, and the
    program's own tree (shapes only)."""
    import jax

    from horovod_tpu.models import transformer
    from yardstick.builders import nemotron_h
    mamba = 2688 * (4096 + 6144 + 64) + 4096 * 2688 + 6144 * 5 + 3 * 64 \
        + 4096 + 2688
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    sparse = 2688 + 2688 * 128 + 128 + 2 * 2688 * 3712 \
        + 8 * 2 * 2688 * 1856
    assert (mamba, attention, sparse) == (38744896, 23399040, 100125440)
    total = 4 * mamba + attention + 4 * sparse + 2 * 16384 * 2688 + 2688
    held = cell()["config"]
    assert held["parameters"] == total == 666963456
    assert total * 16 / 2 ** 30 == pytest.approx(9.94, abs=0.01)
    assert total * 12 / 2 ** 30 == pytest.approx(7.45, abs=0.01)
    cfg = nemotron_h._model_config(cell())
    tree = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == total
    sizes = [sum(x.size for x in jax.tree.leaves(lp))
             for lp in tree["layers"]]
    assert sizes == [{"M": mamba, "E": sparse, "*": attention}[kind]
                     for kind in "MEMEM*EME"]


def test_the_cell_and_its_metrics():
    manifest = mf.load()
    entry = manifest._entry("workloads", CELL)
    assert (entry["chips"], entry["config"]) \
        == (1, "nemotron-3-nano-30b-a3b")
    assert "768" in entry["why"] and "16th" in entry["why"] \
        and len(entry["why"]) <= 200
    spec = manifest.cell(CELL)["spec"]
    assert (spec["job"], spec["seq_len"], spec["batch_per_chip"],
            spec["chunk_steps"], spec["head_block"],
            spec["expert_block_rows"], spec["warmup_chunks"],
            spec["trace_chunks"]) == ("jit_step", 8192, 2, 1, 4096, 512, 2, 2)
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
    for name in ("kda_core_roofline", "linear_attention_ms_per_step",
                 "window_flash_roofline", "allreduce_bus_gbps"):
        assert name not in reported, name
    assert tuple(reported[-3:]) == NEW_METRICS
    assert [m["name"] for m in manifest.bench["per_layer"][-3:]] \
        == list(NEW_METRICS)
    layers = {"state_space_ms_per_step": "step",
              "ssd_core_ms_per_step": "kernels",
              "ssd_core_roofline": "kernels"}
    for name in NEW_METRICS:
        metric = manifest._entry("per_layer", name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "step_ms"
        assert metric["source"] == "device_trace" \
            and metric["layer"] == layers[name]
    assert manifest.layer_metric("ssd_core_roofline") == (
        "scope_roofline", {"kernel": "ssd_core", "scopes": ["hvd.ssd_core"]})
    # the old cells report nothing new, and every list the cell joined
    # still ends with the cells it had
    for old in ("bert-large.dp1-mlm512", "solar-open2-250b.dp1-pt8k",
                "laguna-xs2.dp1-pt8k"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in manifest.metrics("per_layer", old)}
    for metric in manifest.bench["per_layer"]:
        if CELL in metric.get("workloads", ()) \
                and metric["name"] not in NEW_METRICS:
            assert metric["workloads"][-2:] == ["laguna-xs2.dp1-pt8k", CELL]
    # seven cells allow one four-chip cell; the eighth opens a second
    chips = [w["chips"] for w in manifest.bench["workloads"]]
    assert len(chips) == 7 and chips.count(4) == 1 == max(1, 7 // 4)


def test_the_scopes_the_metrics_read_are_the_programs():
    from horovod_tpu.common import scopes
    manifest = mf.load()
    assert manifest.layer_metric("state_space_ms_per_step") == (
        "scope_ms_per_step", {"scopes": [scopes.STATE_SPACE]})
    assert manifest.layer_metric("ssd_core_ms_per_step") == (
        "scope_ms_per_step", {"scopes": [scopes.SSD_CORE]})


# -- end to end, tiny, on the CPU ---------------------------------------------

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
