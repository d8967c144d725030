"""What PR 39 added to the benchmark: the gated delta rule's and the
Olmo-Hybrid-7B share's operation counts against a hand count, the
configuration against the source's published config, the parameter count by
hand against the builder's, the cell and its three metrics in the manifest
(the new cell's membership only, every other expectation derived from the
manifest itself: PERF.md section 7 (a)), the builder's ``kernels`` entries
under the readers the benchmark has, the parent's refusal, and the new cell
end to end at its tiny size on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from yardstick import flops, flops_gated_delta as fg
from yardstick import manifest as mf, peaks
from yardstick.readers import kernel_roofline, scope_roofline

CELL = "olmo-hybrid-7b.dp1-pt8k"
CONFIG = "olmo-hybrid-7b"
NEW_METRICS = ("gated_delta_core_ms_per_step", "gated_delta_core_roofline",
               "dense_ffn_ms_per_step")

# The catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Olmo-Hybrid-7B), as published.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]
    + (["linear_attention"] * 3 + ["full_attention"]) * 7,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "linear_num_key_heads", "linear_num_value_heads",
          "linear_key_head_dim", "linear_value_head_dim",
          "linear_conv_kernel_dim")


def cell():
    return mf.load().cell(CELL)


def shapes():
    from yardstick.builders import olmo_hybrid
    return olmo_hybrid._shapes(cell())


# -- operations and bytes -----------------------------------------------------

def test_a_chunk_of_the_gated_delta_rule_against_a_hand_count():
    """A chunk of 4 steps, keys of 2 over values of 3: the two decayed
    4 x 4 grams 2 x 16 x 2, the solve against [v | k] 16 x 5 / 2, the
    output's P w 16 x 3, and three state-sized products 3 x 4 x 2 x 3."""
    assert fg.gated_delta_macs(4, 2, 3) == 64 + 40 + 48 + 72
    cost = fg.gated_delta_cost(1, 8, 2, 2, 3, 4)
    chunks, rows = 2 * 2, 8 * 2 * 4
    assert cost == {
        "fwd": {"flops": 2.0 * chunks * 224,
                "bytes": rows * (2 * 2 + 2 * 3 + 2)},
        "bwd": {"flops": 4.0 * chunks * 224,
                "bytes": rows * (4 * 2 + 3 * 3 + 4)}}
    # the cell's layer: 1 x 8192 tokens, 30 heads of 96 over 192, chunk 64;
    # bytes set its floor, 1.85 ms a layer at the v5e's peaks
    call = fg.gated_delta_cost(1, 8192, 30, 96, 192, 64)
    v5e = peaks.peak_of("TPU v5 lite")
    floors = [flops.roofline_seconds(c["flops"], c["bytes"], v5e)
              for c in call.values()]
    assert {bound for _, bound in floors} == {"bytes"}
    assert sum(s for s, _ in floors) == pytest.approx(1.851e-3, rel=0.01)


def test_the_models_operations_against_a_hand_count():
    """Per token, forward multiply-adds: a linear layer's projections 3840
    x 30 x (2 x 96 + 3 x 192 + 2) and its convolution 4 x 30 x (2 x 96 +
    192); the full layer's four 3840 x 3840 and 8193 x 30 x 128 for its
    softmax; four SwiGLUs 3 x 3840 x 11008; the head 3840 x 12,544."""
    parts = fg.forward_macs_per_token(**shapes())
    linear = 3840 * 30 * (2 * 96 + 3 * 192 + 2) + 4 * 30 * (2 * 96 + 192)
    assert parts == {
        "head": 3840 * 12544,
        "projections": 3 * linear + 4 * 3840 * 3840,
        "delta_rule": 3 * 30 * fg.gated_delta_macs(64, 96, 192) / 64,
        "softmax": 8193 * 30 * 128,
        "dense": 4 * 3 * 3840 * 11008}
    total = sum(parts.values())
    share = {part: round(100 * macs / total) for part, macs in parts.items()}
    # ISSUE 39's ~919 M a token: the SwiGLUs 55 %, the projections 35, the
    # head 5, causal attention 3.4, the delta rule's core under 1
    assert total == pytest.approx(919e6, rel=2e-3)
    assert share == {"head": 5, "projections": 35, "delta_rule": 1,
                     "softmax": 3, "dense": 55}
    assert fg.train_flops_per_sequence(**shapes()) \
        == pytest.approx(2 * 3 * 8192 * total)


def test_the_builders_kernels_entries():
    from yardstick.builders import olmo_hybrid
    entries = olmo_hybrid._kernels(cell(), 1)
    assert [(k["kernel"], k["calls_per_step"]) for k in entries] == [
        ("gated_delta_core", 3), ("flash", 1)]
    core, flash = entries
    assert core["per_call"] == fg.gated_delta_cost(1, 8192, 30, 96, 192, 64)
    assert flash["per_call"] == flops.flash_attention_cost(
        1, 30, 8192, 128, causal=True)
    ev = {"kernels": entries, "device": {"kind": "TPU v5 lite"}}
    floor, bound = kernel_roofline.floor_seconds(
        ev, {"kernel": "gated_delta_core"})
    assert bound == "bytes" and floor == pytest.approx(3 * 1.851e-3, rel=0.01)
    reader, params = mf.load().layer_metric("gated_delta_core_roofline")
    assert reader == "scope_roofline" \
        and params["kernel"] == "gated_delta_core"
    assert scope_roofline.read(dict(ev, traced=None), params) is None


# -- the manifest -------------------------------------------------------------

def test_the_configuration_is_the_source_cut_as_it_says():
    manifest = mf.load()
    assert manifest.problems() == []
    entry = manifest._entry("configs", CONFIG)
    held = mf.read_json(os.path.join(mf.ROOT, entry["file"]))
    assert entry["source"] == held["source"]
    assert entry["reduced"] == held["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert held[key] != value, key
            assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
            assert key in held["published"], key
        else:
            assert held[key] == value, key
    # one whole period of the published pattern, an eighth of the vocabulary
    period = held["layer_types"]
    assert PUBLISHED["layer_types"] == period * 8
    assert held["num_hidden_layers"] == len(period) == 4
    assert held["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert set(held["assumed"]) >= {
        "linear_layer", "decay", "beta", "recurrence", "output",
        "full_layer", "qk_norm", "norms", "init", "optimizer", "head",
        "loss"}
    assert held["builder"] == "olmo_hybrid"


def test_the_parameter_count_by_hand_against_the_builders():
    """The cut's arithmetic, as the file's ``cut_is`` has it (ISSUE 39's),
    and the program's own tree (shapes only)."""
    import jax

    from horovod_tpu.models import transformer
    from yardstick.builders import olmo_hybrid
    mixer = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 \
        + 4 * (2880 + 2880 + 5760) + 30 + 30 + 192
    swiglu = 3 * 3840 * 11008
    linear = mixer + swiglu + 2 * 3840
    full = 4 * 3840 ** 2 + 2 * 3840 + swiglu + 2 * 3840
    assert (mixer, linear, full) == (88750332, 215570172, 185809920)
    total = 3 * linear + full + 2 * 12544 * 3840 + 3840
    held = cell()["config"]
    assert held["parameters"] == total == 928862196
    assert total * 16 / 2 ** 30 == pytest.approx(13.84, abs=0.01)
    cfg = olmo_hybrid._model_config(cell())
    tree = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == total
    assert [sum(x.size for x in jax.tree.leaves(layer))
            for layer in tree["layers"]] == [linear] * 3 + [full]


def test_the_cell_and_its_metrics():
    manifest = mf.load()
    entry = manifest._entry("workloads", CELL)
    assert (entry["chips"], entry["config"]) == (1, CONFIG)
    assert len(entry["why"]) <= 200
    spec = manifest.cell(CELL)["spec"]
    assert (spec["job"], spec["seq_len"], spec["batch_per_chip"],
            spec["chunk_steps"], spec["head_block"], spec["delta_rule_chunk"],
            spec["warmup_chunks"], spec["trace_chunks"]) \
        == ("jit_step", 8192, 1, 1, 4096, 64, 2, 2)
    assert "14.97 GiB" in spec["batch_is"]
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    # what every cell reports, and what the transformer cells with the
    # delta rule and flash kernels report (derived from the manifest: the
    # lists solar's cell shares with the flash cells)
    everywhere = {m["name"] for m in manifest.bench["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_METRICS) <= reported
    solar = {m["name"] for m in manifest.metrics(
        "per_layer", "solar-open2-250b.dp1-pt8k")}
    flash_cells = {m["name"] for m in manifest.metrics(
        "per_layer", "laguna-xs2.dp1-pt8k")}
    for name in solar & flash_cells:
        if not name.startswith(("moe", "router", "experts")):
            assert name in reported, name
    assert "linear_attention_ms_per_step" in reported
    # nor the per-channel core's, experts', windows', latent or scan metrics
    for name in reported:
        assert not name.startswith(("kda_", "moe", "router", "experts",
                                    "window_", "latent_", "ssd_",
                                    "state_space")), name
    for name in NEW_METRICS:
        metric = manifest._entry("per_layer", name)
        assert CELL in metric["workloads"] and metric["moves"] == "step_ms"
        assert metric["source"] == "device_trace"
    # the feed-forward's metric lists the cells whose pattern has a dense
    # SwiGLU, and only those
    import importlib
    dense = manifest._entry("per_layer", "dense_ffn_ms_per_step")["workloads"]
    for other in manifest.bench["workloads"]:
        name = other["name"]
        c = manifest.cell(name)
        if c["job"] != "jit_step" or c["builder"] in ("bert", "resnet50"):
            assert name not in dense, name
            continue
        cfg = importlib.import_module(
            "yardstick.builders." + c["builder"])._model_config(c)
        assert (name in dense) == any(f == "dense" for _, f in cfg.pairs), \
            name


def test_the_scopes_the_metrics_read_are_the_programs():
    from horovod_tpu.common import scopes
    manifest = mf.load()
    assert manifest.layer_metric("gated_delta_core_ms_per_step") == (
        "scope_ms_per_step", {"scopes": [scopes.GATED_DELTA_CORE]})
    assert manifest.layer_metric("gated_delta_core_roofline") == (
        "scope_roofline", {"kernel": "gated_delta_core",
                           "scopes": [scopes.GATED_DELTA_CORE]})
    assert manifest.layer_metric("dense_ffn_ms_per_step") == (
        "scope_ms_per_step", {"scopes": [scopes.DENSE_FFN]})


def test_a_program_without_the_reordered_norm_is_refused(monkeypatch):
    """The parent's ``TransformerConfig`` has no ``post_norm``: the builder
    says so through ``measure.Refused`` (``run.py`` exits 2) before it
    builds anything."""
    import dataclasses

    from horovod_tpu.models import transformer
    from yardstick import measure
    from yardstick.builders import olmo_hybrid

    @dataclasses.dataclass(frozen=True)
    class Parents:
        vocab_size: int = 0
    monkeypatch.setattr(transformer, "TransformerConfig", Parents)
    with pytest.raises(measure.Refused, match="norm after the sub-layers"):
        olmo_hybrid._model_config(cell())


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
    assert "the program read in float32" in proc.stderr
