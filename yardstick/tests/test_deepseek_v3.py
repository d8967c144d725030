"""What PR 37 added to the benchmark: a latent flash call's and the
Kanana-2-30B-A3B share's operation counts against a hand count, the
configuration against the source's published config, the parameter count by
hand against the builder's, the cell and its three metrics in the manifest
(by name: no other metric's list and not the manifest's last entries, PERF.md
section 7 (a)), the builder's ``kernels`` entries under the readers the
benchmark has, the new cell end to end at its tiny size on the CPU, and the
builder's float32 read reaching ``correct`` through the job's own check."""

import json
import os
import subprocess
import sys

import pytest

from yardstick import flops, flops_hybrid as fh, flops_mla as fm
from yardstick import manifest as mf, peaks
from yardstick.readers import kernel_roofline, scope_roofline

CELL = "kanana-2-30b-a3b.dp1-pt8k"
NEW_METRICS = ("latent_attention_ms_per_step", "latent_flash_ms_per_step",
               "latent_flash_roofline")

# The catalog row's ``config`` (model-configs guide, architectures.jsonl,
# kanana-2-30b-a3b-instruct-2601), as published.
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
WIDTHS = ("hidden_size", "head_dim", "intermediate_size", "kv_lora_rank",
          "moe_intermediate_size", "qk_head_dim", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
          "num_attention_heads", "n_shared_experts")


def cell():
    return mf.load().cell(CELL)


def shapes():
    from yardstick.builders import deepseek_v3
    return deepseek_v3._shapes(cell())


# -- operations and bytes -----------------------------------------------------

def test_a_latent_flash_call_against_a_hand_count():
    """3 sequences, 2 heads, 8 positions, query/key heads of 6 (2 of them
    the rotary key) over values of 4: 3 x 2 x 64 pairs, half under the mask;
    forward 6 + 4 a pair, backward 3 x 6 + 2 x 4."""
    cost = fm.latent_flash_cost(3, 2, 8, 6, 4, causal=True, shared_rope=False)
    pairs, rows = 3 * 2 * 64 / 2, 3 * 2 * 8 * 2
    assert cost == {
        "fwd": {"flops": 2 * pairs * 10, "bytes": rows * (6 + 6 + 4 + 4)},
        "bwd": {"flops": 2 * pairs * 26,
                "bytes": rows * (4 * 6 + 4 * 4)}}
    assert fm.latent_flash_cost(3, 2, 8, 6, 4, False, False)["fwd"]["flops"] \
        == 2 * cost["fwd"]["flops"]
    # the one rotary key read once a batch entry: 2 of a key's 6 a head
    # become 2 a sequence
    shared = fm.latent_flash_cost(3, 2, 8, 6, 4, True, True, rope_dim=2)
    assert shared["fwd"]["flops"] == cost["fwd"]["flops"]
    assert cost["fwd"]["bytes"] - shared["fwd"]["bytes"] \
        == 2 * (rows - 3 * 8 * 2)
    assert cost["bwd"]["bytes"] - shared["bwd"]["bytes"] \
        == 2 * 2 * (rows - 3 * 8 * 2)
    # equal sizes: what flops.flash_attention_cost counts
    same = fm.latent_flash_cost(2, 32, 8192, 128, 128, True, False)
    assert same == flops.flash_attention_cost(2, 32, 8192, 128, causal=True)
    # the cell's call (ISSUE 37): 1.37 and 3.57 T operations, 7.0 and 18.1 ms
    # at the v5e's peak; operations set the floor in both passes
    call = fm.latent_flash_cost(2, 32, 8192, 192, 128, True, True, rope_dim=64)
    assert call["fwd"]["flops"] == 2 * 2 * 32 * 8192 ** 2 / 2 * 320
    assert call["bwd"]["flops"] == 2 * 2 * 32 * 8192 ** 2 / 2 * 832
    v5e = peaks.peak_of("TPU v5 lite")
    floors = {k: flops.roofline_seconds(c["flops"], c["bytes"], v5e)
              for k, c in call.items()}
    assert {bound for _, bound in floors.values()} == {"flops"}
    assert floors["fwd"][0] == pytest.approx(6.98e-3, rel=0.01)
    assert floors["bwd"][0] == pytest.approx(18.14e-3, rel=0.01)


def test_the_models_operations_against_a_hand_count():
    """Per token, forward multiply-adds: a latent block's projections
    2048 x 6144 + 2048 x 576 + 512 x 8192 + 4096 x 2048 = 26,345,472 and
    8193 / 2 keys x 32 heads x (192 + 128); the dense SwiGLU 3 x 2048 x 6144;
    a sparse layer a router 2048 x 128, shared experts 3 x 2048 x 1536 and
    6 x 16 / 128 of a routed expert of 3 x 2048 x 768; the head 2048 x
    16,032."""
    parts = fm.forward_macs_per_token(**shapes())
    projections = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert projections == 26345472
    assert parts == {
        "head": 2048 * 16032,
        "projections": 6 * projections,
        "latent_softmax": 6 * 8193 / 2.0 * 32 * 320,
        "dense": 3 * 2048 * 6144,
        "router": 5 * 2048 * 128,
        "shared_expert": 5 * 3 * 2048 * 1536,
        "routed_experts": 5 * 0.75 * 3 * 2048 * 768}
    total = sum(parts.values())
    share = {part: round(100 * macs / total) for part, macs in parts.items()}
    # the latent attention is 75 % of the model's operations: the core 46,
    # its projections 29
    assert share == {"head": 6, "projections": 29, "latent_softmax": 46,
                     "dense": 7, "router": 0, "shared_expert": 9,
                     "routed_experts": 3}
    assert fm.train_flops_per_sequence(**shapes()) \
        == pytest.approx(2 * 3 * 8192 * total)
    # 53.7 T operations a step of two sequences: 273 ms at the v5e's peak
    assert 2 * 6 * 8192 * total == pytest.approx(53.7e12, rel=2e-3)


def test_the_builders_kernels_entries():
    from yardstick.builders import deepseek_v3
    entries = deepseek_v3._kernels(cell(), 2)
    assert [(k["kernel"], k["calls_per_step"]) for k in entries] == [
        ("experts", 5), ("flash", 6), ("latent_flash", 6)]
    experts, flash, latent = entries
    assert experts["per_call"] == fh.expert_products_cost(12288.0, 16, 2048,
                                                         768)
    call = fm.latent_flash_cost(2, 32, 8192, 192, 128, True, True, rope_dim=64)
    assert flash["per_call"] == latent["per_call"] == call
    # what the readers make of them: six calls' floor is 150.7 ms a step
    # (the recomputation's second forward pass is not in it)
    ev = {"kernels": entries, "device": {"kind": "TPU v5 lite"}}
    for name in ("flash", "latent_flash"):
        floor, bound = kernel_roofline.floor_seconds(ev, {"kernel": name})
        assert bound == "flops"
        assert floor == pytest.approx(6 * (6.98e-3 + 18.14e-3), rel=0.01)
    assert kernel_roofline.floor_seconds(ev, {"kernel": "ssd_core"}) is None
    # no trace, nothing to read
    reader, params = mf.load().layer_metric("latent_flash_roofline")
    assert reader == "scope_roofline" and params["kernel"] == "latent_flash"
    assert scope_roofline.read(dict(ev, traced=None), params) is None


# -- what the builder sets before the first step ------------------------------

def test_the_cells_load_profile():
    """The 16 held experts' loads as the cell's file gives them: the 12,288
    pairs an even routing gives this chip, nemotron's eight values twice
    over, 34 blocks of 512 rows a layer and none within 64 tokens of a
    block's end."""
    from yardstick.builders import deepseek_v3
    spec = cell()["spec"]
    goal = deepseek_v3.load_targets(cell(), 2 * 8192)
    assert goal.shape == (128,) and goal.sum() == pytest.approx(6 * 16384)
    held = goal[:16]
    assert held.sum() == pytest.approx(12288) and goal.mean() == 768
    assert [round(n) for n in held] == [2112, 1229, 883, 691, 576, 384, 192,
                                        77] * 2
    rows = spec["expert_block_rows"]
    blocks = [int(-(-n // rows)) for n in held]
    assert blocks == [5, 3, 2, 2, 2, 1, 1, 1] * 2 and sum(blocks) == 34
    assert min(min(n % rows, rows - n % rows) for n in held) >= 64
    assert set(goal[16:].round(6)) == {768.0}


# -- the manifest -------------------------------------------------------------

def test_the_configuration_is_the_source_cut_as_it_says():
    manifest = mf.load()
    assert manifest.problems() == []
    entry = manifest._entry("configs", "kanana-2-30b-a3b")
    held = mf.read_json(os.path.join(mf.ROOT, entry["file"]))
    assert entry["source"] == held["source"]
    assert entry["reduced"] == held["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert held[key] != value, key
            assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
            assert held["published"][key] == value, key
        else:
            assert held[key] == value, key
    # the floors: the leading dense layer and four or more after it, 8
    # routed experts or more, an eighth of the vocabulary
    assert held["num_hidden_layers"] - held["first_k_dense_replace"] >= 4
    assert held["n_routed_experts"] >= 8
    assert held["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert set(held["assumed"]) >= {
        "rotary", "query_latent", "shared_experts", "router", "norms",
        "attention", "init", "optimizer", "head", "loads", "loss"}
    assert held["builder"] == "deepseek_v3"


def test_the_parameter_count_by_hand_against_the_builders():
    """The cut's arithmetic, as the file's ``cut_is`` has it (ISSUE 37's),
    and the program's own tree (shapes only)."""
    import jax

    from horovod_tpu.models import transformer
    from yardstick.builders import deepseek_v3
    mla = 2048 * 32 * 192 + 2048 * (512 + 64) + 512 + 512 * 32 * 256 \
        + 32 * 128 * 2048
    dense = mla + 2 * 2048 + 3 * 2048 * 6144
    sparse = mla + 2 * 2048 + 2048 * 128 + 128 + 3 * 2048 * 1536 \
        + 16 * 3 * 2048 * 768
    assert (mla, dense, sparse) == (26345984, 64098816, 111547008)
    total = dense + 5 * sparse + 2 * 16032 * 2048 + 2048
    held = cell()["config"]
    assert held["parameters"] == total == 687502976
    assert total * 16 / 2 ** 30 == pytest.approx(10.25, abs=0.01)
    assert total * 12 / 2 ** 30 == pytest.approx(7.68, abs=0.01)
    cfg = deepseek_v3._model_config(cell())
    tree = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == total
    assert sum(x.size for x in jax.tree.leaves(tree["leading"])) == dense
    assert sum(x.size for x in jax.tree.leaves(tree["layers"])) == 5 * sparse


def test_the_cell_and_its_metrics():
    manifest = mf.load()
    entry = manifest._entry("workloads", CELL)
    assert (entry["chips"], entry["config"]) == (1, "kanana-2-30b-a3b")
    assert "768" in entry["why"] and "8th" in entry["why"] \
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
                 "model_flops_util", "device_idle_share", "init_s",
                 "state_build_s", "trace_lower_s", "executable_load_s",
                 "setup_programs", "setup_in_program_s") + NEW_METRICS:
        assert name in reported, name
    for name in ("kda_core_roofline", "linear_attention_ms_per_step",
                 "window_flash_roofline", "window_attention_ms_per_step",
                 "ssd_core_roofline", "allreduce_bus_gbps"):
        assert name not in reported, name
    layers = {"latent_attention_ms_per_step": "step",
              "latent_flash_ms_per_step": "kernels",
              "latent_flash_roofline": "kernels"}
    for name in NEW_METRICS:
        metric = manifest._entry("per_layer", name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "step_ms"
        assert metric["source"] == "device_trace" \
            and metric["layer"] == layers[name]
    # the old cells report nothing new
    for old in manifest.bench["workloads"]:
        if old["name"] != CELL:
            assert not set(NEW_METRICS) & {
                m["name"] for m in manifest.metrics("per_layer", old["name"])}


def test_the_scopes_the_metrics_read_are_the_programs():
    from horovod_tpu.common import scopes
    manifest = mf.load()
    assert manifest.layer_metric("latent_attention_ms_per_step") == (
        "scope_ms_per_step", {"scopes": [scopes.LATENT_ATTENTION]})
    kernels = [scopes.FLASH_FWD, scopes.FLASH_DQ, scopes.FLASH_DKV,
               scopes.FLASH_BWD_ONEPASS]
    assert manifest.layer_metric("latent_flash_ms_per_step") == (
        "scope_ms_per_step", {"scopes": kernels})
    assert manifest.layer_metric("latent_flash_roofline") == (
        "scope_roofline", {"kernel": "latent_flash", "scopes": kernels})


def test_a_program_without_the_mixer_is_refused():
    """The parent's ``models/transformer.py`` has no ``LatentAttention``:
    the builder says so through ``measure.Refused`` (``run.py`` exits 2)."""
    from horovod_tpu.models import transformer
    from yardstick import measure
    from yardstick.builders import deepseek_v3
    kind = transformer.LatentAttention
    try:
        del transformer.LatentAttention
        with pytest.raises(measure.Refused, match="latent-attention"):
            deepseek_v3._model_config(cell())
    finally:
        transformer.LatentAttention = kind


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


def test_a_float32_read_off_the_reference_is_not_correct():
    """The builder's finer comparison reaches the result line through the
    job's own check: a program whose float32 read is twice ``FLOAT32_RTOL``
    off the reference is handed no room for its step-0 loss (at the tiny
    size that room is 0.3, which the bfloat16 step needs)."""
    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "from yardstick.builders import deepseek_v3 as B\n"
        "read = B.float32_loss\n"
        "B.float32_loss = lambda *a: read(*a) * (1 + 2 * B.FLOAT32_RTOL)\n"
        "from yardstick import rehearse\n"
        "sys.exit(rehearse.one_on_cpu(%r, 1.0))\n" % (mf.ROOT, CELL))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], cwd=mf.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 1, proc.stderr[-2000:]
    line = json.loads([ln for ln in proc.stdout.splitlines()
                       if ln.startswith("{")][-1])
    failed = [name for name, held in line["checks"].items() if not held]
    assert not line["correct"] and len(failed) == 1 \
        and "within 0 of the plain reference" in failed[0], line["checks"]
    assert "the program read in float32" in proc.stderr
