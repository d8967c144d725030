"""The operation counts against ``bench.py``'s constants and a count by
hand."""

import sys

import pytest

from yardstick import flops, manifest, peaks


def test_resnet50_is_bench_py():
    sys.path.insert(0, manifest.ROOT)
    try:
        import bench
    finally:
        sys.path.remove(manifest.ROOT)
    assert flops.RESNET50_GFLOPS_FWD == bench.MODEL_GFLOPS_FWD["resnet50"]
    assert flops.TRAIN_FLOP_MULT == bench.TRAIN_FLOP_MULT
    assert flops.resnet50_train_flops() == pytest.approx(4.089e9 * 3)
    assert flops.resnet50_train_flops(112) == pytest.approx(4.089e9 * 3 / 4)
    assert peaks.peak_of("TPU v5 lite")["bf16_flops_per_s"] == \
        bench.PEAK_FLOPS_BY_KIND["TPU v5 lite"]


def test_bert_large_by_hand():
    """BERT-Large, one full sequence of 512 tokens, 77 of them predicted.
    Per layer and token, in multiply-adds: projections 4 x 1024^2 =
    4,194,304; feed-forward 2 x 1024 x 4096 = 8,388,608; attention
    2 x 512 x 1024 = 1,048,576; together 13,631,488.  24 layers x 512
    tokens = 167,503,724,544.  Head: 77 x (1024^2 + 1024 x 30522) =
    77 x 32,303,104 = 2,487,339,008.  Sum 169,991,063,552; x 2 operations
    x 3 for training = 1,019,946,381,312 a sequence."""
    enc = flops.bert_encoder_macs(512, 1024, 24, 4096)
    assert enc == 167_503_724_544
    head = flops.bert_mlm_head_macs(77, 1024, 30522)
    assert head == 2_487_339_008
    assert flops.bert_train_flops([512], 1024, 24, 4096, head) == \
        1_019_946_381_312
    # Padding is not work: two sequences of 100 and 300 real tokens.
    short = [flops.bert_encoder_macs(t, 1024, 24, 4096) for t in (100, 300)]
    assert short[0] == 24 * 100 * (4_194_304 + 8_388_608 + 2 * 100 * 1024)
    cls = flops.bert_cls_head_macs(1024, 2)
    assert cls == 1024 * 1024 + 2048
    assert flops.bert_train_flops([100, 300], 1024, 24, 4096, cls) == \
        6.0 * (sum(short) + 2 * cls) / 2


def test_flash_cost_and_roofline():
    """16 heads of 64 over 4 sequences of 512, not causal: one product is
    2 x 4 x 16 x 512^2 x 64 = 2,147,483,648 operations; forward 2 of
    them, backward 5.  One tensor is 4 x 16 x 512 x 64 x 2 B = 4 MiB."""
    cost = flops.flash_attention_cost(4, 16, 512, 64, causal=False)
    assert cost["fwd"] == {"flops": 2 * 2_147_483_648, "bytes": 4 * (4 << 20)}
    assert cost["bwd"] == {"flops": 5 * 2_147_483_648, "bytes": 8 * (4 << 20)}
    causal = flops.flash_attention_cost(4, 16, 512, 64, causal=True)
    assert causal["fwd"]["flops"] == cost["fwd"]["flops"] / 2
    peak = peaks.peak_of("TPU v5 lite")
    secs, bound = flops.roofline_seconds(197e12, 1.0, peak)
    assert (secs, bound) == (1.0, "flops")
    secs, bound = flops.roofline_seconds(1.0, 819e9 * 2, peak)
    assert (secs, bound) == (2.0, "bytes")


def test_bus_bytes_and_unknown_peak():
    assert flops.allreduce_bus_bytes(100.0, 4) == 150.0
    assert flops.allreduce_bus_bytes(100.0, 1) == 0.0
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_of("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_of("_source")
