"""What the sparse-attention configuration adds to the benchmark: the
share's operation counts against a hand count, the configuration against
the source's published config, the cell and its five metrics in the
manifest (the new cell's membership only, every other expectation derived
from the manifest itself), ``builders/keye_vl2.py``'s ``kernels`` entries
under the readers the benchmark has, and the refusal of a program without
the mixer."""

import pytest

from yardstick import flops, flops_sparse as fs
from yardstick import manifest as mf, measure, peaks
from yardstick.readers import kernel_roofline

CELL = "keye-vl2-30b-a3b.dp1-pt32k"
CONFIG = "keye-vl2-30b-a3b"
NEW_METRICS = ("sparse_attention_ms_per_step", "indexer_ms_per_step",
               "indexer_topk_ms_per_step", "sparse_flash_ms_per_step",
               "sparse_flash_roofline")
# The source's ``config.json`` for the language model
# (Kwai-Keye/Keye-VL-2.0-30B-A3B), as published.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def cell():
    return mf.load().cell(CELL)


def shapes():
    from yardstick.builders import keye_vl2
    return keye_vl2._shapes(cell())


def test_a_tokens_forward_operations_against_a_hand_count():
    """Per token and layer, forward: projections, router and held experts
    52.2 MFLOP; the indexer over every causal key 33.6; attention over the
    kept keys 32.5; the head 77.8."""
    parts = fs.forward_macs_per_token(**shapes())
    per_layer = {k: 2 * v / 4 / 1e6 for k, v in parts.items() if k != "head"}
    assert per_layer["projections"] + per_layer["router"] \
        + per_layer["routed_experts"] == pytest.approx(52.2, abs=0.05)
    assert per_layer["indexer"] == pytest.approx(33.6, abs=0.05)
    assert per_layer["sparse_softmax"] == pytest.approx(32.5, abs=0.05)
    assert 2 * parts["head"] / 1e6 == pytest.approx(77.8, abs=0.05)


def test_the_pairs_and_the_kernels_floor():
    assert fs.scored_pairs(4) == 10 and fs.kept_pairs(4, 2) == 1 + 2 + 2 + 2
    assert fs.kept_pairs(32768, 2048) / 32768 == pytest.approx(1984.03,
                                                              abs=0.01)
    cost = fs.sparse_flash_cost(1, 32, 4, 32768, 128, 2048)
    # 4 x 32 x 128 operations a kept pair forward: 1.06 TFLOP a layer
    assert cost["fwd"]["flops"] == pytest.approx(1.065e12, rel=1e-3)
    v5e = peaks.peak_of("TPU v5 lite")
    floors = [flops.roofline_seconds(c["flops"], c["bytes"], v5e)
              for c in cost.values()]
    assert {bound for _, bound in floors} == {"flops"}
    assert 4 * sum(s for s, _ in floors) == pytest.approx(75.7e-3, rel=0.01)


def test_the_configuration_is_the_published_one_but_what_it_reduces():
    c = cell()["config"]
    entry = next(e for e in mf.load().bench["configs"]
                 if e["name"] == CONFIG)
    assert c["source"] == entry["source"]
    assert set(entry["reduced"]) == set(c["reduced"])
    for key, value in PUBLISHED.items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key


def test_the_cell_and_its_metrics_are_in_the_manifest():
    manifest = mf.load()
    (entry,) = [w for w in manifest.bench["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for m in manifest.bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "step_ms"
    assert set(NEW_METRICS) <= reported
    for name in ("attention_ms_per_step", "head_ms_per_step",
                 "experts_roofline", "router_rows_ms_per_step", "init_s",
                 "forward_ms_per_step"):
        assert name in reported, name
    assert "flash_roofline" not in reported


def test_the_builders_kernels_are_what_the_readers_read():
    from yardstick.builders import keye_vl2
    entries = keye_vl2._kernels(cell(), 1)
    assert [e["kernel"] for e in entries] == ["experts", "sparse_flash"]
    ev = {"kernels": entries, "device": {"kind": "TPU v5 lite"}}
    secs, bound = kernel_roofline.floor_seconds(
        ev, {"kernel": "sparse_flash"})
    assert bound == "flops" and secs == pytest.approx(75.7e-3, rel=0.01)


def test_a_program_without_the_mixer_is_refused(monkeypatch):
    from horovod_tpu.models import transformer
    from yardstick.builders import keye_vl2
    monkeypatch.delattr(transformer, "SparseAttention")
    with pytest.raises(measure.Refused, match="learned-sparse-attention"):
        keye_vl2._model_config(cell())
