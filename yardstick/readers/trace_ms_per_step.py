"""Device time a step, in ms, summed over the operations the params
select on the traced chip, each operation's own time (what is nested in
it taken out):

* ``{"select": "compute"}``     everything that is not a collective
* ``{"select": "collective"}``  all-reduce / all-gather / reduce-scatter /
                                collective-permute / all-to-all
* ``{"select": "match", "pattern": RE}``  operations whose name, opcode
                                or custom-call target matches ``RE``

A trace with no such operation reads 0, which is how a cell shows that it
bypasses them; no trace reads nothing."""

import re


def selected_seconds(red, params):
    if params["select"] == "compute":
        return red["compute_s"]
    if params["select"] == "collective":
        return red["collective_s"]
    pattern = re.compile(params["pattern"])
    return sum(op["seconds"] for op in red["ops"].values()
               if any(pattern.search(op["info"][key])
                      for key in ("name", "opcode", "target")))


def read(ev, params):
    traced = ev.get("traced") or {}
    if "reduction" not in traced:
        return None
    return 1e3 * selected_seconds(traced["reduction"], params) \
        / traced["steps"]
