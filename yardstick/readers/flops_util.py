"""Model FLOP/s utilisation in percent: samples a second a chip of the
run's window, times the operations a sample needs (``flops.py``;
recomputation not counted), over the chip's published bf16 peak."""

from yardstick import peaks, report


def read(ev, params):
    peak = peaks.peak_of(ev["device"]["kind"])
    return 100.0 * report.samples_per_s_per_chip(ev) \
        * ev["flops_per_sample"] / peak["bf16_flops_per_s"]
