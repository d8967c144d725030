"""From a run's evidence to the result line.

End-to-end metrics are the benchmark's own arithmetic on its own clock and
on ``memory_stats()``; per-layer metrics are read by the small readers
under ``readers/``, each found by the name its ``layer_metrics/*.json``
gives.  A reader that finds nothing to read returns ``None`` and its
metric is left out.
"""

import statistics

from yardstick import peaks
from yardstick import trace as tr

GIB = float(1 << 30)


def step_ms(ev):
    """Median over chunks of a chunk's wall time over its steps."""
    return 1e3 * statistics.median(s / n for s, n in ev["chunks"])


def samples_per_s_per_chip(ev):
    """Samples every rank completed in the whole window, stalls and all,
    over the window and the chips."""
    return ev["samples_per_step"] * ev["steps"] / ev["window_s"] / ev["chips"]


def peak_hbm_gib(ev):
    return max(ev["peak_bytes"]) / GIB


def setup_s(ev):
    """Process start to the first measured step."""
    return ev["t_window"] - ev["t_start"]


END_TO_END = {"step_ms": step_ms,
              "samples_per_s_per_chip": samples_per_s_per_chip,
              "peak_hbm_gib": peak_hbm_gib, "setup_s": setup_s}


def result_line(manifest, cell_name, ev, traced, rehearsal=False):
    """The one JSON object a run prints last.  A rehearsal's device has no
    published peaks; there, and only there, a metric that needs them is
    left out."""
    metrics = {}
    if not traced:
        for m in manifest.metrics("end_to_end", cell_name):
            metrics[m["name"]] = {"value": END_TO_END[m["name"]](ev),
                                  "unit": m["unit"]}
    else:
        for m in manifest.metrics("per_layer", cell_name):
            reader, params = manifest.layer_metric(m["name"])
            try:
                value = manifest.module("readers", reader).read(ev, params)
            except peaks.UnknownDevice:
                if not rehearsal:
                    raise
                value = None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(ev["device"], memory_peak_bytes=max(ev["peak_bytes"]))
    line = {"correct": all(ev["checks"].values()),
            "attempted": ev["steps"], "failed": ev["failed_steps"],
            "metrics": metrics, "device": device}
    red = (ev.get("traced") or {}).get("reduction")
    if traced and red:
        # Averaged over the chips used; the per-layer metrics and the
        # breakdown are the first chip's.
        device["busy_s"] = statistics.fmean(red["busy_s_by_chip"])
        device["window_s"] = red["window_s"]
        line["breakdown"] = {
            "device_ops": tr.top({o["info"]["label"]: o["seconds"]
                                  for o in red["ops"].values()}),
            "idle_gaps": tr.top(red["idle_by_span"])}
        line["trace_lines"] = red["describe"]
    # Beyond the contract, for the reader of a log: what was checked, and
    # the run's own end-to-end numbers whatever the mode.
    line["checks"] = ev["checks"]
    line["losses"] = ev["losses"]
    line["run"] = {name: fn(ev) for name, fn in END_TO_END.items()}
    line["run"].update(window_s=ev["window_s"], chunks=len(ev["chunks"]),
                       first_step_s=ev["first_step_s"], cache=ev["cache"],
                       peak_bytes=ev["peak_bytes"],
                       peak_bytes_after_reference=ev.get(
                           "peak_bytes_after_reference"))
    return line
