"""A kernel's share of its roofline in percent: the least time the chip
could take for the kernel's calls of one step, which is the larger of
operations over peak FLOP/s and bytes over peak bytes/s (``flops.py``,
from the call's shapes at the model's own head size), over the device
time the kernel's operations took.  ``params``: ``kernel`` names the
builder's entry, ``pattern`` selects its operations in the trace.
``bound(ev, params)`` says which peak sets the floor."""

from yardstick import flops, peaks
from yardstick.readers import trace_ms_per_step


def floor_seconds(ev, params):
    """(seconds a step, which bound) or None where the cell has no such
    kernel."""
    calls = [k for k in ev["kernels"] if k["kernel"] == params["kernel"]]
    if not calls:
        return None
    peak = peaks.peak_of(ev["device"]["kind"])
    secs, bounds = 0.0, set()
    for k in calls:
        for cost in k["per_call"].values():
            s, bound = flops.roofline_seconds(cost["flops"], cost["bytes"],
                                              peak)
            secs += k["calls_per_step"] * s
            bounds.add(bound)
    return secs, "+".join(sorted(bounds))


def read(ev, params):
    traced = ev.get("traced") or {}
    floor = floor_seconds(ev, params)
    if "reduction" not in traced or floor is None:
        return None
    took = trace_ms_per_step.selected_seconds(
        traced["reduction"], {"select": "match",
                              "pattern": params["pattern"]}) / traced["steps"]
    return 100.0 * floor[0] / took if took else None
