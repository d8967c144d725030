"""Device time a step, in ms, of the non-collective operations that the
program traced under the scopes the params select (``yardstick/scopes.py``
says how an operation finds its scope), each operation's own time:

* ``{"phase": P}``         ``forward`` / ``backward`` / ``optimizer`` /
                           ``exchange`` / ``unscoped``: the five split the
                           non-collective operations, so they add up to
                           what ``trace_ms_per_step`` calls ``compute``
* ``{"scopes": [S, ...]}`` operations with any of these ``hvd.*`` scopes
                           on their path, whatever the phase

A scope no operation carries reads 0, which is how a cell shows that it
bypasses it.  No trace, or a program that names nothing at all (one from
before the scopes) reads nothing.

The job leaves the reduced trace in the evidence and the trace itself on
disk; the first metric read looks the operations' names up in that file
(``scopes.traced_op_names``) and leaves them in the evidence, beside the
reduction, for the others."""

import glob
import os

from yardstick import manifest as mf
from yardstick import scopes
from yardstick import trace as tr

TRACES = os.path.join(mf.ROOT, "yardstick_out", "*", "trace", "plugins",
                      "profile", "*", "*.xplane.pb")


def traced_since(t):
    """The newest ``.xplane.pb`` a run has written under its
    ``yardstick_out/<cell>/trace`` since ``t`` (``time.time()``), or
    ``None``: every cell keeps its last trace there, and this run's is the
    one written after its own window began."""
    found = [p for p in glob.glob(TRACES) if os.path.getmtime(p) >= t]
    return max(found, key=os.path.getmtime, default=None)


def op_names(ev):
    """``scopes.merge``'s answer for this run's traced operations."""
    traced = ev["traced"]
    if "op_names" not in traced:
        path = traced_since(ev.get("t_window", float("inf")))
        traced["op_names"] = path and scopes.traced_op_names(
            path, traced["reduction"]["ops"])
    return traced["op_names"]


def selected_seconds(red, names, params):
    if ("phase" in params) == ("scopes" in params) \
            or params.get("phase", "forward") not in scopes.PHASES:
        raise ValueError("params select one phase of %s or a list of "
                         "scopes, not %r" % (scopes.PHASES, params))
    want = set(params.get("scopes", ()))
    total = 0.0
    for name, op in red["ops"].items():
        if tr.is_collective(name):
            continue
        phase, found = scopes.classify(names.get(name, ""))
        if phase == params.get("phase") or want & found:
            total += op["seconds"]
    return total


def read(ev, params):
    traced = ev.get("traced") or {}
    if "reduction" not in traced:
        return None
    names = (op_names(ev) or {}).get("names")
    if names is None \
            or not any(scopes.SCOPE.search(n) for n in names.values()):
        return None
    return 1e3 * selected_seconds(traced["reduction"], names, params) \
        / traced["steps"]
