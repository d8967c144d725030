"""From a device operation to the part of the step that asked for it.

The program names the parts of its compiled step with ``jax.named_scope``
(``horovod_tpu/common/scopes.py``: ``hvd.model``, ``hvd.optimizer``,
``hvd.exchange``, ``hvd.attention``, ``hvd.head`` and one scope for each
flash kernel).  The profiler's events do not carry those names on this
stack (jax 0.9.0, libtpu 0.0.34): an event is named by its HLO instruction
and nothing else.  The optimized HLO of the program does carry them: every
instruction has ``metadata={op_name="jit(step)/jvp(hvd.model)/dot_general"}``,
the backward pass reads ``transpose(jvp(hvd.model))``, scopes nest with
``/``, and a fusion carries the ``op_name`` of its root.  Instruction names
there (``fusion.189``) are the names the trace's events start with, so

    event -> instruction name -> op_name -> phase and scopes

joins the per-instruction own times of ``trace.reduce_chip`` to the scopes.
A fusion that XLA built from operations of two parts goes whole to the part
of its root: the attribution is by instruction, not by arithmetic.

The HLO is the trace's own: the profiler writes into the ``.xplane.pb``,
on a plane of no lines named ``/host:metadata``, the ``HloProto`` of every
program that was loaded while it traced.  ``jax.profiler.ProfileData``
shows lines and events only, so the few fields wanted are taken from the
protobuf wire format directly.  Plain bytes and text handling; no jax.
"""

import re
import time

# ``  ROOT %fusion.1 = f32[8]{0} fusion(...), ..., metadata={op_name="..."}``
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+)\s+=\s")
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
SCOPE = re.compile(r"hvd\.[a-z0-9_]+")

MODEL, OPTIMIZER, EXCHANGE = "hvd.model", "hvd.optimizer", "hvd.exchange"
# In the order one gives way to the next where an instruction has several.
PHASES = ("unscoped", "forward", "backward", "optimizer", "exchange")


def op_names(hlo_text):
    """``{instruction name: op_name}`` over every computation of one HLO
    module as ``to_string()`` / ``as_text()`` prints it: the entry, the
    bodies and conditions of ``while`` loops, fused computations.  Names
    are unique in a module.  An instruction printed with no ``op_name``
    (a copy or a bitcast XLA put in) maps to ``""``."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            found = OP_NAME.search(line, m.end())
            out[m.group(1)] = found.group(1) if found else ""
    return out


def _phase(path):
    if EXCHANGE in path:
        return "exchange"
    if OPTIMIZER in path:
        return "optimizer"
    if MODEL not in path:
        return "unscoped"
    before = path[:path.index(MODEL)]
    return "backward" if "transpose(" in before else "forward"


def classify(op_name):
    """``(phase, scopes)`` of one ``op_name``.

    ``phase`` is ``exchange`` if the path holds ``hvd.exchange``, else
    ``optimizer`` if it holds ``hvd.optimizer``, else ``backward`` or
    ``forward`` if it holds ``hvd.model`` with or without a ``transpose(``
    before it, else ``unscoped``.  XLA joins the names of operations it
    merged with ``;``: the last in ``PHASES`` that any of them has is the
    instruction's.  ``scopes`` is every ``hvd.*`` name on the path,
    wrapped by a transformation (``jvp(hvd.flash_fwd)``) or not."""
    phase = max(map(_phase, op_name.split(";")), key=PHASES.index)
    return phase, set(SCOPE.findall(op_name))


def merge(seen, modules):
    """The ``op_name`` of each instruction name in ``seen`` (what a trace
    reduction saw), from ``modules``: a list of ``op_names`` maps, one for
    each HLO module the trace holds.  Instruction names repeat from module
    to module (every module has a ``fusion.1``), so the module that holds
    most of ``seen`` is taken as the step and answers for every name it
    holds; the others only fill what is left.  Returns ``names`` with
    ``main_share`` (the share of ``seen`` the step's module held), ``filled`` (how many
    names came from another module), ``ambiguous`` (how many of those were
    in more than one of them) and ``missing`` (the names no module
    holds)."""
    seen = list(seen)
    main = max(modules, key=lambda m: sum(n in m for n in seen), default={})
    rest = [m for m in modules if m is not main]
    names, missing, filled, ambiguous = {}, [], 0, 0
    for name in seen:
        if name in main:
            names[name] = main[name]
            continue
        holders = [m for m in rest if name in m]
        if not holders:
            missing.append(name)
            continue
        names[name] = holders[0][name]
        filled += 1
        ambiguous += len(holders) > 1
    return {"names": names,
            "main_share": (len(seen) - filled - len(missing)) / len(seen)
            if seen else 0.0,
            "filled": filled, "ambiguous": ambiguous, "missing": missing}


# -- the trace's own HLO ---------------------------------------------------

METADATA_PLANE = "/host:metadata"


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def submessages(buf, number):
    """The length-delimited values (strings, bytes, messages) of field
    ``number`` of one serialized protobuf message, as views of ``buf``."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            _, i = _varint(buf, i)
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        elif kind == 2:
            size, i = _varint(buf, i)
            if key >> 3 == number:
                yield buf[i:i + size]
            i += size
        else:
            raise ValueError("wire type %d at byte %d" % (kind, i))


def _text(buf, number):
    return "".join(bytes(v).decode() for v in submessages(buf, number))


def proto_op_names(hlo_proto):
    """``op_names`` of one serialized ``HloProto``: ``hlo_module`` (1) ->
    ``computations`` (3) -> ``instructions`` (2) -> ``name`` (1) and
    ``metadata`` (7) -> ``op_name`` (2)."""
    out = {}
    for module in submessages(hlo_proto, 1):
        for computation in submessages(module, 3):
            for instruction in submessages(computation, 2):
                out[_text(instruction, 1)] = "".join(
                    _text(m, 2) for m in submessages(instruction, 7))
    return out


def trace_hlo_protos(xspace):
    """The serialized ``HloProto`` of every program a serialized ``XSpace``
    holds: ``planes`` (1) named (2) ``METADATA_PLANE`` -> the values (2) of
    ``event_metadata`` (4) -> ``stats`` (5) -> ``bytes_value`` (6)."""
    for plane in submessages(xspace, 1):
        if _text(plane, 2) != METADATA_PLANE:
            continue
        for entry in submessages(plane, 4):
            for metadata in submessages(entry, 2):
                for stat in submessages(metadata, 5):
                    yield from submessages(stat, 6)


def traced_op_names(xplane_path, seen):
    """``merge`` over the HLO modules the trace at ``xplane_path`` holds,
    with the ``seconds`` that took: the step is among them because it ran
    while the profiler did."""
    t0 = time.perf_counter()
    with open(xplane_path, "rb") as f:
        xspace = memoryview(f.read())
    modules = [proto_op_names(p) for p in trace_hlo_protos(xspace)]
    out = merge(seen, modules)
    out["modules"] = len(modules)
    out["seconds"] = time.perf_counter() - t0
    return out
