"""From a profiler trace to numbers: busy and idle time of a chip, time by
operation, collectives against compute, and idle gaps named by what the
host was doing.

Two stages.  ``read_xplane`` turns the profiler's ``.xplane.pb`` into a
plain structure (it needs jax, nothing else does)::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns, stats],
                                       ...]}]}]}

On this stack (jax 0.9.0, libtpu 0.0.34) a device event's name is its whole
HLO instruction, ``%fusion.189 = bf16[4,512,4096]{...} fusion(...)``, and a
Mosaic kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``; ``while`` operations hold their
bodies' operations nested inside them; asynchronous copies sit on a line of
their own and are not counted as the core being busy.

Everything after that is arithmetic on that structure, checked in
``tests/test_trace.py`` against a recorded trace kept in ``fixtures/``
with answers worked out by hand.  Times inside are nanoseconds on the
profiler's clock, which device and host lines share; results are seconds.
"""

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# HLO instruction names of operations that move data between chips.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?(\.|$)")
ASYNC_PAIR = re.compile(r"^(.*)-(start|done)((\.\d+)?)$")
# Host spans kept from the trace: the benchmark's own and the program's.
SPAN_PREFIXES = ("yardstick.", "hvd.")
# An HLO instruction as the trace prints it: name, shape, opcode(operands).
OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]*)"')
# The profiler rounds an event's start and duration to whole nanoseconds
# and keeps the device's own picosecond readings as statistics.
PS_START, PS_DURATION = "device_offset_ps", "device_duration_ps"
SHORT_GAP_NS = 10_000
SHORT_GAPS = "(gaps under 10 us)"
NO_SPAN = "(no span)"


class TraceError(Exception):
    """The trace does not hold what the reduction needs."""


# -- reading ---------------------------------------------------------------

def find_xplane(trace_dir):
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if len(found) != 1:
        raise TraceError("%d .xplane.pb files under %s" % (len(found),
                                                           trace_dir))
    return found[0]


def read_xplane(path, keep_stats=False):
    """Device lines whole, and of the host lines the spans whose names
    start with ``SPAN_PREFIXES``.  ``keep_stats`` keeps the statistics of
    device events, which the reduction does not read: for looking at a
    trace by hand."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                if device:
                    stats = dict(e.stats)
                    start, duration = e.start_ns, e.duration_ns
                    if PS_START in stats and PS_DURATION in stats:
                        start = int(stats[PS_START]) / 1000.0
                        duration = int(stats[PS_DURATION]) / 1000.0
                    stats = {k: v for k, v in stats.items()
                             if keep_stats
                             and isinstance(v, (str, int, float))}
                    events.append([e.name, start, duration, stats])
                elif e.name.startswith(SPAN_PREFIXES):
                    events.append([e.name, e.start_ns, e.duration_ns, {}])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace):
    """Planes and lines with their event counts: what to look at by hand
    before trusting a reduction of a new kind of trace."""
    return [(p["name"], ln["name"], len(ln["events"]))
            for p in trace["planes"] for ln in p["lines"]]


# -- selecting -------------------------------------------------------------

def chips(trace):
    return sorted(int(DEVICE_PLANE.match(p["name"]).group(1))
                  for p in trace["planes"] if DEVICE_PLANE.match(p["name"]))


def parse_op(text):
    """An event's name into what selects and names the operation: the
    instruction's ``name`` without its ``%``, its ``opcode``, a custom
    call's ``target``, and a ``label`` short enough to print.  A name that
    is no HLO text is kept as it is."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return {"name": text, "opcode": "", "target": "", "label": text}
    opcode = OPCODE.search(" " + rest)
    target = TARGET.search(rest)
    info = {"name": name.lstrip("%"),
            "opcode": opcode.group(1) if opcode else "",
            "target": target.group(1) if target else ""}
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    info["label"] = "%s %s %s" % (
        info["name"], info["target"] or info["opcode"], shape[:60])
    return info


def device_ops(trace, chip):
    """``(name, start, end, info)`` of every operation on one chip's op
    line, by start and outer before inner; ``info`` as ``parse_op``
    gives it."""
    for p in trace["planes"]:
        m = DEVICE_PLANE.match(p["name"])
        if m and int(m.group(1)) == chip:
            for ln in p["lines"]:
                if ln["name"] == OP_LINE:
                    parsed = {}
                    out = []
                    for n, s, d, _ in ln["events"]:
                        if n not in parsed:
                            parsed[n] = parse_op(n)
                        out.append((parsed[n]["name"], s, s + d, parsed[n]))
                    return sorted(out, key=lambda e: (e[1], -e[2]))
    raise TraceError("no line %r on a plane of chip %d; the trace holds %s"
                     % (OP_LINE, chip, describe(trace)))


def async_ops(trace, chip):
    """``(name, start, end)`` of the asynchronous operations under way on
    one chip: each from its ``-start`` to its ``-done``, as the trace's
    own line for them has it.  Empty where the trace has no such line."""
    for p in trace["planes"]:
        m = DEVICE_PLANE.match(p["name"])
        if m and int(m.group(1)) == chip:
            for ln in p["lines"]:
                if ln["name"] == ASYNC_LINE:
                    return [(parse_op(n)["name"], s, s + d)
                            for n, s, d, _ in ln["events"]]
    return []


def host_spans(trace):
    """``(name, start, end)`` of every kept host span, all threads."""
    return sorted(((n, s, s + d)
                   for p in trace["planes"]
                   if not DEVICE_PLANE.match(p["name"])
                   for ln in p["lines"] for n, s, d, _ in ln["events"]),
                  key=lambda e: e[1])


def span_window(trace, name):
    """First start and last end of the host spans called ``name``."""
    found = [(s, e) for n, s, e in host_spans(trace) if n == name]
    if not found:
        raise TraceError("no host span %r in the trace" % name)
    return min(s for s, _ in found), max(e for _, e in found)


# -- interval arithmetic ---------------------------------------------------

def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [tuple(i) for i in out]


def total(intervals):
    return sum(b - a for a, b in intervals)


def complement(disjoint, lo, hi):
    """What ``[lo, hi]`` holds outside sorted disjoint intervals."""
    out, at = [], lo
    for a, b in disjoint:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(ops):
    """Each operation's own time: its duration less that of the operations
    nested in it (a ``while`` holds its body's).  ``ops`` by start, outer
    before inner; returns a list in the same order."""
    own = [e - s for _, s, e, _ in ops]
    stack = []
    for i, (_, s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


# -- the reduction ---------------------------------------------------------

def is_collective(name):
    return bool(COLLECTIVE.match(name))


def collective_flight(ops, asyncs=()):
    """Intervals in which a collective is under way: a synchronous one
    for its duration, an asynchronous one from the start of its
    ``-start`` to the end of its ``-done``, taken from the op line's own
    pair and from the trace's line of asynchronous operations."""
    out, open_ = [(s, e) for n, s, e in asyncs if is_collective(n)], {}
    for name, s, e, _ in ops:
        if not is_collective(name):
            continue
        pair = ASYNC_PAIR.match(name)
        if not pair:
            out.append((s, e))
        elif pair.group(2) == "start":
            open_[pair.group(1) + pair.group(3)] = s
        else:
            out.append((open_.pop(pair.group(1) + pair.group(3), s), e))
    return union(out)


def reduce_chip(trace, chip, window):
    """One chip inside ``window`` = (lo, hi).  Seconds throughout:

    ``window_s``, ``busy_s`` (union of the intervals in which an operation
    ran), ``idle_gaps`` (what is left, as intervals in ns),
    ``collective_s`` (own time of
    collectives: the op stream runs one operation at a time, so this is
    the time it spent in or waiting on them), ``compute_s`` (own time of
    everything else), ``collective_flight_s`` (time a collective was under
    way, hidden behind compute or not) and ``ops`` (by operation name: own
    ``seconds``, ``count`` and ``info`` as ``parse_op`` gives it, for
    readers that select by what names an operation).
    """
    lo, hi = window
    ops = [(n, max(s, lo), min(e, hi), st)
           for n, s, e, st in device_ops(trace, chip)
           if min(e, hi) > max(s, lo)]
    asyncs = [(n, max(s, lo), min(e, hi)) for n, s, e in
              async_ops(trace, chip) if min(e, hi) > max(s, lo)]
    own = self_times(ops)
    busy = union((s, e) for _, s, e, _ in ops)
    ns = 1e-9
    by_op = {}
    for (name, _, _, info), t in zip(ops, own):
        row = by_op.setdefault(name, {"seconds": 0.0, "count": 0,
                                      "info": info})
        row["seconds"] += t * ns
        row["count"] += 1
    coll = sum(t for (n, _, _, _), t in zip(ops, own) if is_collective(n))
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": total(busy) * ns,
        "idle_gaps": complement(busy, lo, hi),
        "collective_s": coll * ns,
        "compute_s": (sum(own) - coll) * ns,
        "collective_flight_s": total(collective_flight(ops, asyncs)) * ns,
        "ops": by_op,
    }


def name_gaps(gaps, spans):
    """Seconds of idle time by what the host was doing.  Each gap goes to
    the shortest host span that covers at least half of it (an inner span
    says more than the one round it), or else to the one that covers most.
    Gaps under 10 us are summed under one name."""
    out = collections.Counter()
    spans = sorted(spans, key=lambda s: s[1])
    for lo, hi in gaps:
        if hi - lo < SHORT_GAP_NS:
            out[SHORT_GAPS] += hi - lo
            continue
        half, most = None, (0, NO_SPAN)
        for name, s, e in spans:
            if s >= hi:
                break
            covered = min(e, hi) - max(s, lo)
            if covered <= 0:
                continue
            most = max(most, (covered, name))
            if 2 * covered >= hi - lo and (half is None or e - s < half[0]):
                half = (e - s, name)
        out[half[1] if half else most[1]] += hi - lo
    return {n: t * 1e-9 for n, t in out.items()}


def top(seconds_by_name, n=10):
    """The ``n`` largest, as the result line's ``breakdown`` wants them."""
    return [[name, secs] for name, secs in sorted(
        seconds_by_name.items(), key=lambda kv: -kv[1])[:n]]
